"""CPU tests of the benchmark: its arithmetic, its generator, its lookup
by name and its correctness check, at sizes a test run can hold. They
never describe or touch a TPU."""
import copy
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

jax.config.update("jax_platform_name", "cpu")

# one limit for the tiny cells below: the served path reads gaps of a
# few thousandths at this size, the fp8 control a tenth or more
TINY_LIMIT = 0.05


def shrink(cell):
    """The cell at a size the CPU runs in seconds: every width and the
    depth cut, the same mix shape with short prompts and outputs."""
    cell = copy.deepcopy(cell)
    m = cell.config["model"]
    m.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, num_hidden_layers=2,
             vocab_size=512)
    if "image_tokens" in m:
        m.update(image_tokens=64, vision_feature_dim=32)
    cell.config["serving"].update(prefill_chunk=32, max_len=256, max_batch=4)
    cell.config["check"]["max_logit_gap"] = TINY_LIMIT
    t = cell.traffic
    if t["image_share"]:
        t.update(burst=4, table=8)
        t["output_tokens"].update(min=4, max=24, median=8)
    else:
        t.update(burst=8, table=8)
        t["text_tokens"].update(median=40, min=8, max=120)
        t["output_tokens"].update(min=8, max=64)
    t.update(warmup_hold=16, trace_after_s=0.2, trace_s=1.0,
             check_tokens=64)
    return cell


@pytest.fixture
def tiny_cell():
    from bench import harness

    def make(name):
        return shrink(harness.resolve(name))
    return make
