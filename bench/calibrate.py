#!/usr/bin/env python3
"""Readings that set a cell's ``max_logit_gap`` limit, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ...

For each seed: weights drawn from it, one burst of the cell's traffic
from it through the served path at the cell's own load, the
same sample the benchmark compares, and two readings: the program's
``max_logit_gap`` (the lower reading, over a dozen seeds or more) and the
fp8 control's (the upper reading: the reference computed with float8
e4m3 operands, put in the program's place). Each reading is also judged
by the benchmark's own rule (``check.verdict``) against the committed
limit: the program's has to come out correct, the control's not. The
benchmark's runs never run the control. One process serves every seed:
the cluster is built once and each seed's weights are put into its
engines, with the prefix cache emptied in between so that no page of one
seed's weights serves another's. Prints one JSON line per seed and a
summary line last.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def readings_over_seeds(cell, seeds):
    """Yield, seed by seed, the readings of ``check.readings`` on what the
    served path produced for that seed's weights and traffic."""
    import jax.numpy as jnp
    import numpy as np

    from bench import check, harness, reference
    from repro.core.cluster import EPDCluster

    doc, traffic = cell.config, cell.traffic
    serving = doc["serving"]
    cfg = harness.model_config(doc)
    dtype = jnp.dtype(doc["dtype"])
    image_tokens = doc["model"].get("image_tokens", 0)
    gen = harness.generator(traffic)
    sizes = dict(image_tokens=image_tokens, max_len=serving["max_len"])
    arch = reference.Arch.from_doc(doc["model"])

    params = harness.make_weights(cfg, seeds[0], dtype)
    cluster = EPDCluster(cfg, params, **serving)
    warm = [harness.to_request(s, image_tokens) for s in gen.warmup(
        traffic, vocab=cfg.vocab, page=serving["page_size"], **sizes)]
    cluster.run_continuous(warm)
    engines = ([cluster.prefill_engine] + cluster.decode_engines
               + cluster.encode_engines)
    for seed in seeds:
        t0 = time.perf_counter()
        params = harness.make_weights(cfg, seed, dtype)
        for e in engines:
            e.params = params
        pc = cluster.prefill_engine.prefix_cache
        if pc is not None:
            pc.evict(cluster.prefill_engine.pool.n_pages)
            if pc.retained_pages():
                raise RuntimeError("prefix cache still holds pages")
        reqs = [harness.to_request(s, image_tokens) for s in next(
            gen.bursts(traffic, seed, vocab=cfg.vocab, **sizes))]
        done = cluster.run_continuous(reqs)
        ids = {r.request_id for r in done}
        served = harness._served(reqs, ids, image_tokens)
        rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([int(seed), 2])))
        sample = check.sample(served, rng, traffic["check_tokens"])
        got = check.readings(params, arch, sample,
                             length=serving["max_len"],
                             n_read=int(traffic["output_tokens"]["max"]),
                             control=True)
        failed = len(reqs) - len(ids)
        limit = doc["check"]["max_logit_gap"]
        control = dict(got, max_logit_gap=got["control_max_logit_gap"])
        got.update(
            seed=seed, failed=failed, seconds=time.perf_counter() - t0,
            correct=check.verdict(
                check.compared(got, limit, failed=failed), got),
            control_correct=check.verdict(
                check.compared(control, limit, failed=failed), control))
        yield got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[0] = str(REPO)
    sys.path.insert(1, str(REPO / "src"))
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = harness.resolve(args.workload)
    lower, upper = 0.0, float("inf")
    correct = control_correct = 0
    for got in readings_over_seeds(cell, args.seeds):
        lower = max(lower, got["max_logit_gap"])
        upper = min(upper, got["control_max_logit_gap"])
        correct += got["correct"]
        control_correct += got["control_correct"]
        print(json.dumps(got), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(args.seeds),
                      "lower": lower, "upper": upper,
                      "limit": cell.config["check"]["max_logit_gap"],
                      "correct": correct,
                      "control_correct": control_correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
