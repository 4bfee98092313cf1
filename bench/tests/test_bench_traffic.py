"""The burst generator: the same seed gives the same requests, every seed
the same sizes in the same order, and the stated ranges hold."""
import itertools

import pytest

from bench import harness

MIXES = ["sharegpt4o-burst16", "chat-longout-batch48"]
SIZES = dict(vocab=32064, image_tokens=2880, max_len=4096)


def _mix(name):
    doc = harness.read_json(harness.BENCH / "traffic" / f"{name}.json")
    return doc, harness.generator(doc)


def _take(gen, doc, seed, n):
    return list(itertools.islice(gen.bursts(doc, seed, **SIZES), n))


def _sizes(bursts):
    return [[(len(s.prompt), s.max_new, s.image is not None) for s in b]
            for b in bursts]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    doc, gen = _mix(name)
    seed = 2**31 + 12345                # larger than 32 signed bits hold
    assert _take(gen, doc, seed, 4) == _take(gen, doc, seed, 4)
    assert _take(gen, doc, seed, 2) != _take(gen, doc, seed + 1, 2)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_sizes(name):
    doc, gen = _mix(name)
    per_pass = doc["table"] // doc["burst"]
    ref = _sizes(_take(gen, doc, 1, per_pass))
    for seed in (2, 99, 2**33):
        assert _sizes(_take(gen, doc, seed, per_pass)) == ref


@pytest.mark.parametrize("name", MIXES)
def test_ranges_hold(name):
    doc, gen = _mix(name)
    lo, hi = doc["text_tokens"]["min"], doc["text_tokens"]["max"]
    olo, ohi = doc["output_tokens"]["min"], doc["output_tokens"]["max"]
    head = gen.template(doc, SIZES["vocab"])
    images = set()
    for b in _take(gen, doc, 7, 6):
        assert len(b) == doc["burst"]
        for s in b:
            assert s.prompt[:len(head)] == head
            assert lo <= len(s.prompt) - len(head) <= hi
            assert olo <= s.max_new <= ohi
            assert all(3 <= t < SIZES["vocab"] for t in s.prompt)
            n = len(s.prompt) + (SIZES["image_tokens"] if s.image else 0)
            assert n + s.max_new < SIZES["max_len"]
            if s.image:
                assert s.image not in images      # one image per request
                images.add(s.image)
                assert s.image_pos == len(head)
    share = doc["image_share"]
    assert (len(images) > 0) == (share > 0)


def test_sharegpt4o_text_follows_the_paper():
    doc, gen = _mix("sharegpt4o-burst16")
    table = gen.size_table(doc, image_tokens=2880, max_len=4096)
    text = [t - doc["template_tokens"] for t, _, _ in table]
    assert abs(sum(text) / len(text) - 9.6) < 0.5
    assert all(img for _, _, img in table)


@pytest.mark.parametrize("name", MIXES)
def test_warmup_covers_every_page_class(name):
    doc, gen = _mix(name)
    page = 16
    table = gen.size_table(doc, image_tokens=2880, max_len=4096)
    want = {(img, -(-(t + (2880 if img else 0)) // page))
            for t, _, img in table}
    warm = gen.warmup(doc, page=page, **SIZES)
    classes = [(s.image is not None,
                -(-(len(s.prompt) + (2880 if s.image else 0)) // page))
               for s in warm]
    # every class is served once after the first request, which found
    # the prefix cache empty
    assert set(classes[1:]) == want
    slots = doc["warmup_slots"]
    assert all(s.max_new == doc["warmup_hold"] for s in warm[:slots])
    assert all(s.max_new == 2 for s in warm[slots:])
    assert len(warm) == max(slots, 1) + len(want) - len(set(classes[1:slots]))


def test_a_prompt_that_leaves_no_room_is_refused():
    doc, gen = _mix("sharegpt4o-burst16")
    with pytest.raises(ValueError):
        gen.size_table(doc, image_tokens=2880, max_len=2048)
