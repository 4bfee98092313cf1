"""Each configuration's model family, ``bench/models/<reference>.py``:
the reference, work counts and weight rules the harness takes from it.

The dense family wraps ``bench/reference.py`` and ``bench/work.py``
without changing a number: every per-layer reader reads, on the two
recorded v5e traces, the values the harness read before the families
existed (written here as constants), and the weights are the same bits.
A family of another shape is added with files alone: a toy hybrid (one
attention layer, one SSM layer) in a directory of its own brings its
reference, its counts of attention layers and its weight rule."""
import copy
import hashlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, harness, reference, stats, trace_reduce as T
from bench.tests.conftest import shrink

HERE = Path(__file__).resolve().parent
DOC = harness.read_json(harness.REPO / "BENCHMARK.json")
CONFIGS = {c["name"]: harness.read_json(harness.REPO / c["file"])
           for c in DOC["configs"]}
PER_LAYER = [m["name"] for m in DOC["per_layer"]]
V5E = harness.peaks("TPU v5 lite")

# what the traced part holds besides the trace: the recorded prefill
# window (the last of a 2.9k-token image request) and decode steps
INPUTS = {
    "trace_v5e.json": dict(
        chunks=[(2560, 2896, True)],
        decode_lengths=[[2897, 1301, 45, 17]],
        counts={"prefill_tokens_computed": 336.0,
                "sched_admissions_total": 1.0, "sched_stalls_total": 3.0,
                "sched_chunks_total": 1.0, "bench.decode_steps": 1.0,
                "encode_tokens_total": 2880.0}),
    "trace_v5e_spans.json": dict(
        chunks=[],
        decode_lengths=[[300 + 37 * i + s for i in range(16)]
                        for s in range(3)],
        counts={"bench.decode_steps": 3.0, "sched_stalls_total": 0.0,
                "sched_chunks_total": 0.0}),
}

# every per-layer reader's value on those inputs before the readers read
# the family's counts (``harness.dims`` and ``bench.work`` called from
# each reader)
PARENT = {
    ("trace_v5e.json", "llava-next-mistral-7b-d8"): {
        "decode_occupancy_pct": 50.0,
        "prefill_ms_per_ktok": 51.23459226190476,
        "kv_copy_ms_per_req": 0.4862870000000001,
        "decode_step_ms": 35.74626,
        "flash_attention_roofline": 8.639806550391008,
        "prefill_step_mfu": 38.12534216835338,
        "paged_decode_attention_roofline": 1.847641441276842,
        "decode_step_mfu": 0.22106865036529993,
        "mfu_pct": 10.831729279610224,
        "device_idle_pct": 14.417074554926613,
        "prefill_stall_pct": 75.0},
    ("trace_v5e.json", "deepseek-7b-d6"): {
        "decode_occupancy_pct": 50.0,
        "prefill_ms_per_ktok": 51.23459226190476,
        "kv_copy_ms_per_req": 0.4862870000000001,
        "decode_step_ms": 35.74626,
        "flash_attention_roofline": 6.479854912793256,
        "prefill_step_mfu": 26.742927563508157,
        "paged_decode_attention_roofline": 5.527368876710328,
        "decode_step_mfu": 0.19153934936804903,
        "mfu_pct": 7.481027854243057,
        "device_idle_pct": 14.417074554926613,
        "prefill_stall_pct": 75.0},
    ("trace_v5e_spans.json", "llava-next-mistral-7b-d8"): {
        "decode_occupancy_pct": 50.0,
        "prefill_ms_per_ktok": None,
        "kv_copy_ms_per_req": None,
        "decode_step_ms": 39.51606233333333,
        "flash_attention_roofline": None,
        "prefill_step_mfu": None,
        "paged_decode_attention_roofline": 10.954429316558334,
        "decode_step_mfu": 0.7868093945680429,
        "mfu_pct": 0.6024091803144646,
        "device_idle_pct": 23.41860322174646,
        "prefill_stall_pct": None},
    ("trace_v5e_spans.json", "deepseek-7b-d6"): {
        "decode_occupancy_pct": 50.0,
        "prefill_ms_per_ktok": None,
        "kv_copy_ms_per_req": None,
        "decode_step_ms": 39.51606233333333,
        "flash_attention_roofline": None,
        "prefill_step_mfu": None,
        "paged_decode_attention_roofline": 32.69403496452646,
        "decode_step_mfu": 0.6832370025117828,
        "mfu_pct": 0.5231104832824169,
        "device_idle_pct": 23.41860322174646,
        "prefill_stall_pct": None},
}

# sha256 of every leaf's bits, in tree order, of the weights drawn from
# seed 2**31 + 5 at the tests' reduced size (``conftest.shrink``) before
# the families existed
PARENT_WEIGHTS = {
    "llava-sharegpt4o-burst16":
        "ce92e56ef82d644c18357d5107ff505d241d9685b6121faa267eb5c01c52f970",
    "deepseek-chat-batch48":
        "49eaf0bb2f3fc9b27340739a73241f1702d301a7c5467bb5e5ee4dfe14bee5ed",
}


def _recorded(name):
    doc = harness.read_json(HERE / name)
    dev = [T.Event(doc["device"], "XLA " + line, n, float(s), float(d))
           for line, n, s, d in doc["rows"] if line != "Span"]
    window = doc.get("window_ns") or (
        max(e.start_ns + e.dur_ns for e in dev)
        - min(e.start_ns for e in dev))
    evs = T.attach(dev)
    return window * 1e-9, evs, T.reduce(evs)


def _ctx(trace, doc, work):
    window_s, evs, red = _recorded(trace)
    return harness.Context(
        stats.Window(start=0.0), setup_s=1.0, occupancy=0.5, work=work,
        peaks=V5E, image_tokens=doc["model"].get("image_tokens", 0),
        window_s=window_s, trace=red, events=evs, gauges={},
        **copy.deepcopy(INPUTS[trace]))


@pytest.mark.parametrize("trace,config", sorted(PARENT))
@pytest.mark.parametrize("metric", PER_LAYER)
def test_readers_read_the_parents_values(trace, config, metric):
    doc = CONFIGS[config]
    ctx = _ctx(trace, doc, harness.model_module(doc).work(doc))
    assert harness.reader(metric).read(ctx) == PARENT[trace, config][metric]


def _digest(params):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(PARENT_WEIGHTS))
def test_dense_weights_are_the_parents_bits(cell):
    doc = shrink(harness.resolve(cell)).config
    params = harness.make_weights(harness.model_config(doc), 2**31 + 5,
                                  jnp.bfloat16, harness.model_module(doc))
    assert _digest(params) == PARENT_WEIGHTS[cell]


def test_dense_reference_is_the_direct_call():
    doc = shrink(harness.resolve("deepseek-chat-batch48")).config
    model = harness.model_module(doc)
    params = harness.make_weights(harness.model_config(doc), 3,
                                  jnp.bfloat16, model)
    kw = dict(seq=list(range(5, 40)), read=[30, 31, 34], length=64,
              n_read=4)
    via = model.logits(params, model.Arch.from_doc(doc["model"]), **kw)
    direct = reference.logits(params, reference.Arch.from_doc(doc["model"]),
                              **kw)
    assert via.shape == (3, doc["model"]["vocab_size"])
    np.testing.assert_array_equal(via, direct)


def test_an_unknown_family_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError, match="no-such-family"):
        harness.model_module({"reference": "no-such-family"})
    with pytest.raises(FileNotFoundError):
        harness.model_module({"reference": "dense"}, models=tmp_path)


# ---- a family of another shape, added with files alone ---------------------

TOY = '''
"""A toy hybrid: the dense family's widths, one attention layer and one
SSM layer; a reference that records its calls."""
import jax.numpy as jnp
import numpy as np

from bench import harness

dense = harness.load_module(harness.BENCH / "models" / "dense.py")
Arch = dense.Arch
CALLS = []
A_LOG = 0.375


def logits(params, arch, *, seq, read, image=None, image_pos=0, length,
           n_read, quant=None):
    CALLS.append((len(seq), tuple(read), quant))
    return np.zeros((len(read), arch.vocab), np.float32)


def work(doc):
    return dense.Counts(dense.dims(doc), attn_layers=1)


init_rules = {"ssm_a": lambda key, shape, dtype: jnp.full(shape, A_LOG,
                                                          dtype)}
'''

# the toy's registry config, as a family's own configuration module would
# add it: two layers, attention then SSM, at the registry's SSM widths
TOY_CONFIG = dict(
    name="toy-hybrid", arch_type="hybrid", n_layers=2, d_model=4096,
    n_heads=32, n_kv_heads=8, d_ff=14336, vocab=65536,
    pattern=(("attn", "mlp"), ("ssm", "mlp")))

TOY_DOC = {
    "registry": "toy-hybrid",
    "reference": "toy",
    "dtype": "bfloat16",
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
              "tie_word_embeddings": False},
    "program": {"ssm.state_dim": 16, "ssm.head_dim": 16,
                "ssm.chunk_size": 32},
}


@pytest.fixture
def toy_registry(monkeypatch):
    from repro import configs
    from repro.configs import LayerSpec, ModelConfig, SSMConfig
    cfg = ModelConfig(**dict(
        TOY_CONFIG, pattern=tuple(LayerSpec(*p) for p in
                                  TOY_CONFIG["pattern"])), ssm=SSMConfig())
    monkeypatch.setitem(configs._MODULES, cfg.name, "toy_hybrid")
    monkeypatch.setitem(configs._cache, cfg.name, cfg)
    return cfg


@pytest.fixture
def toy(tmp_path, toy_registry):
    (tmp_path / "toy.py").write_text(TOY)
    return harness.model_module(TOY_DOC, models=tmp_path)


def test_program_block_reaches_the_program(toy_registry):
    cfg = harness.model_config(TOY_DOC)
    assert [(s.mixer, s.ffn) for s in cfg.pattern] == [("attn", "mlp"),
                                                       ("ssm", "mlp")]
    assert (cfg.ssm.state_dim, cfg.ssm.head_dim, cfg.ssm.chunk_size) == (
        16, 16, 32)
    assert cfg.attn_layers == (0,) and cfg.ssm_layers == (1,)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (2, 64, 256)


@pytest.mark.parametrize("path", ["no_such_field", "ssm.no_such_field",
                                  "ssm.state_dim.deeper",
                                  "frontend.feature_dim"])
def test_an_unknown_program_field_is_an_error(toy_registry, path):
    doc = dict(TOY_DOC, program={path: 1})
    with pytest.raises(KeyError, match="program override"):
        harness.model_config(doc)


@pytest.mark.parametrize("path,value", [
    ("ssm", 3), ("pattern", "attn"), ("ssm", {"state_dim": 16}),
    ("pattern", [{"mixer": "attn", "ffn": "mlp"}]),
    ("moe", {"n_experts": 9}), ("ssm.state_dim", [16])])
def test_a_program_override_sets_one_scalar(toy_registry, path, value):
    """A group (a dataclass, or a tuple of them) is not set whole, and a
    field takes a number, a string or a boolean."""
    doc = dict(TOY_DOC, program={path: value})
    with pytest.raises(TypeError, match="program override"):
        harness.model_config(doc)


def test_toy_weights_use_its_init_rule(toy):
    cfg = harness.model_config(TOY_DOC)
    params = harness.make_weights(cfg, 4, jnp.bfloat16, toy)
    attn, ssm = params["blocks"]
    assert "attn" in attn and "ssm" in ssm
    a_log = np.asarray(ssm["ssm"]["a_log"], np.float32)
    assert a_log.shape == (1, cfg.ssm.n_heads(cfg.d_model))
    assert (a_log == toy.A_LOG).all()
    # the dense family has no rule for it: an error naming both
    dense = harness.model_module({"reference": "dense"})
    with pytest.raises(ValueError, match="ssm_a.*dense.py"):
        harness.make_weights(cfg, 4, jnp.bfloat16, dense)


@pytest.mark.parametrize("metric", ["flash_attention_roofline",
                                    "paged_decode_attention_roofline"])
def test_toy_kernels_count_its_attention_layers_only(toy, metric):
    dense = harness.model_module({"reference": "dense"})
    read = harness.reader(metric).read
    two = read(_ctx("trace_v5e.json", TOY_DOC, dense.work(TOY_DOC)))
    one = read(_ctx("trace_v5e.json", TOY_DOC, toy.work(TOY_DOC)))
    assert two > 0
    assert one == pytest.approx(two / 2, rel=1e-12)


def test_check_reads_the_toys_reference(toy):
    served = [check.Served([1, 2, 3, 4], None, 0, 0, [5, 6, 7])]
    got = check.readings(None, toy, toy.Arch.from_doc(TOY_DOC["model"]),
                         served, length=16, n_read=4, control=True)
    assert toy.CALLS == [(6, (3, 4, 5), None), (6, (3, 4, 5), "fp8")]
    assert got["max_logit_gap"] == 0.0 and got["tokens_compared"] == 3
