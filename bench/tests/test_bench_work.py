"""bench/work.py against counts made by hand."""
import pytest

from bench import work

D = work.Dims(n_layers=2, d_model=8, n_heads=4, n_kv_heads=2, head_dim=2,
              d_ff=16, vocab=10, feature_dim=3)


def test_layer_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 x3
    assert D.layer_params == 64 + 32 + 32 + 64 + 3 * 128


@pytest.mark.parametrize("start,end,keys", [(0, 1, 1), (0, 4, 10),
                                            (2, 4, 7), (3, 3, 0)])
def test_causal_keys(start, end, keys):
    assert work.causal_keys(start, end) == keys
    assert keys == sum(p + 1 for p in range(start, end))


def test_causal_keys_refuses_a_bad_span():
    with pytest.raises(ValueError):
        work.causal_keys(3, 2)


def test_flash_chunk_by_hand():
    # positions 2, 3 attend 3 + 4 keys; 4 heads x head dim 2 x (QK + PV)
    flops, nbytes = work.flash_chunk(D, 2, 4)
    assert flops == 4 * 4 * 2 * 7
    # q and out: 2 tokens x 4 heads x 2; k and v: 4 tokens x 2 kv heads x 2
    assert nbytes == 2 * (2 * 2 * 8 + 2 * 4 * 4)


def test_paged_decode_counts_true_lengths_only():
    flops, nbytes = work.paged_decode(D, [3, 5])
    assert flops == 4 * 4 * 2 * (3 + 5)
    assert nbytes == 2 * sum(2 * n * 2 * 2 + 2 * 4 * 2 for n in (3, 5))
    assert work.paged_decode(D, []) == (0, 0)


def test_model_flops_by_hand():
    lp = D.layer_params
    head = 2 * 8 * 10
    assert work.prefill_chunk_flops(D, 0, 3, last=False) == \
        2 * (2 * lp * 3 + 4 * 4 * 2 * 6)
    assert work.prefill_chunk_flops(D, 0, 3, last=True) == \
        2 * (2 * lp * 3 + 4 * 4 * 2 * 6) + head
    assert work.decode_step_flops(D, [4]) == 2 * (2 * lp + 4 * 4 * 2 * 4) \
        + head
    assert work.encode_flops(D, 5) == 2 * 5 * 3 * 8


def test_roofline_names_its_bound():
    assert work.roofline_s(200, 1, 100.0, 10.0) == (2.0, "compute")
    assert work.roofline_s(1, 50, 100.0, 10.0) == (5.0, "memory")
