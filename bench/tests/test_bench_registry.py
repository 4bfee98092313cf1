"""Everything a cell is made of is found by name, and the command
refuses to run off a TPU."""
import os
import subprocess
import sys

import pytest

from bench import harness, reference

DOC = harness.read_json(harness.REPO / "BENCHMARK.json")
CELLS = [w["name"] for w in DOC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cells_resolve_by_name(name):
    cell = harness.resolve(name)
    assert cell.config["registry"]
    harness.generator(cell.traffic)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end:
        assert callable(harness.reader(m["name"]).read)
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]).read)
        assert m["moves"] in names


@pytest.mark.parametrize("name", [c["name"] for c in DOC["configs"]])
def test_config_sizes_reach_the_program(name):
    entry = next(c for c in DOC["configs"] if c["name"] == name)
    doc = harness.read_json(harness.REPO / entry["file"])
    assert entry["reduced"] == doc["reduced"]
    cfg = harness.model_config(doc)
    m = doc["model"]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab, cfg.norm_eps) == (
        m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
        m["vocab_size"], m["rms_norm_eps"])
    assert reference.Arch.from_doc(m).n_layers == cfg.n_layers
    for k in doc["reduced"]:
        assert m[k] != doc["published"][k]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell")


def test_run_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.REPO, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_compile_watch_counts_new_shapes_only_while_on(capfd):
    import jax
    import jax.numpy as jnp
    watch = harness.CompileWatch()
    f = jax.jit(lambda x: x * 3)
    a, b, c = jnp.ones(5), jnp.ones(6), jnp.ones(7)
    f(a)                                          # before: not counted
    capfd.readouterr()
    watch.start()
    f(a)                                          # cached: not counted
    f(b)                                          # a new shape
    watch.stop()
    assert "Compiling" not in capfd.readouterr().err   # nothing printed
    f(c)                                          # after: not counted
    assert watch.count == 1
    assert len(watch.names) == 1 and "float32[6]" in watch.names[0]


def test_a_reader_that_finds_nothing_is_left_out():
    from bench import stats
    ctx = harness.Context(stats.Window(start=0.0), setup_s=1.0)
    metrics = [{"name": "decode_occupancy_pct", "unit": "%"},
               {"name": "setup_s", "unit": "s"}]
    assert harness.read_metrics(metrics, ctx, required=False) == {
        "setup_s": {"value": 1.0, "unit": "s"}}
    with pytest.raises(RuntimeError):
        harness.read_metrics(metrics, ctx, required=True)


def test_registry_totals_sum_labels():
    from repro.core.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("a_total", site="x").inc(2)
    reg.counter("a_total", site="y").inc(3)
    reg.gauge("g").set(7)
    assert harness.registry_totals(reg, "counters") == {"a_total": 5}
    assert harness.registry_totals(reg, "gauges") == {"g": 7}
