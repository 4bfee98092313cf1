"""Serving launcher: real-compute EPD-disaggregated serving, or the
paper-scale event simulator for any deployment topology.

  # real tensors through the full EPD pipeline (reduced model, CPU-sized):
  PYTHONPATH=src python -m repro.launch.serve --arch llava-next-mistral-7b \\
      --requests 8

  # published widths cut to 8 layers, bf16, the chip deployment (TPU):
  PYTHONPATH=src python -m repro.launch.serve --layers 8 --requests 8

  # paper-scale simulation of a deployment at a given request rate:
  PYTHONPATH=src python -m repro.launch.serve --simulate --deployment "(E-P)-D" \\
      --rate 8 --arch openpangu-7b-vl
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

# The one-chip deployment chip_smoke.py serves at published widths: paged
# KV, chunked prefill and the radix prefix cache, as the continuous loop
# runs them. The decode pool holds every slot at max_len (~2.3 GB of
# bf16 KV at 8 mistral-7b layers).
CHIP_SERVING = dict(paged=True, chunked_prefill=True, prefix_cache=True,
                    page_size=16, prefill_chunk=512, max_len=4096,
                    max_batch=16)


def build_cluster(cfg, *, dtype, seed: int = 0, **cluster_kw):
    """The one way to build a served model: random ``dtype`` weights for
    ``cfg`` from ``seed``, served by an EPDCluster (the KV pools take the
    weights' dtype). Returns the cluster."""
    from repro.core.cluster import EPDCluster
    from repro.models.model import init_params

    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)
    return EPDCluster(cfg, params, **cluster_kw)


def run_real(args):
    from repro.configs import get_config
    from repro.serving.request import Request

    cfg = get_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
        dtype, serving = jnp.bfloat16, CHIP_SERVING
    else:
        cfg = cfg.reduced()
        dtype, serving = jnp.float32, dict(max_batch=4, max_len=96)
    cluster = build_cluster(cfg, dtype=dtype, kv_scheme=args.kv_scheme,
                            **serving)
    n_img = cfg.frontend.tokens_per_item if cfg.frontend is not None else 0
    reqs = []
    for i in range(args.requests):
        mm = (f"image-{i % 3}".encode()
              if cfg.frontend is not None and i % 2 == 0 else None)
        reqs.append(Request(
            prompt_tokens=list(range(2, 2 + 8 + i % 5)),
            max_new_tokens=args.max_new_tokens,
            mm_payload=mm, mm_tokens=n_img if mm and cfg.encoder is None
            else 0))
    done = cluster.run_continuous(reqs)
    for r in done:
        path = "E-P-D" if r.is_multimodal else "P-D"
        print(f"req {r.request_id} [{path}] -> {r.output_tokens}")
    s = cluster.store.stats
    print(f"MM store: {s} | mean KV overlap: "
          f"{cluster.report.mean_kv_overlap:.3f} | recomputes: "
          f"{cluster.report.recomputes}")


def run_sim(args):
    from repro.configs import get_config
    from repro.core.simulator import SHAREGPT_4O, VISUALWEB, simulate

    ds = SHAREGPT_4O if args.dataset == "sharegpt4o" else VISUALWEB
    model = get_config(args.arch)
    m = simulate(model, args.deployment, ds, rate=args.rate,
                 n_requests=args.requests, kv_scheme=args.kv_scheme,
                 per_chip_rate=args.per_chip_rate)
    print(f"deployment={m.deployment} chips={m.n_chips}")
    print(f"TTFT mean={m.mean_ttft_ms:.1f}ms p99={m.p99_ttft_ms:.1f}ms")
    print(f"TPOT mean={m.mean_tpot_ms:.2f}ms p99={m.p99_tpot_ms:.2f}ms")
    print(f"throughput={m.throughput_tok_s:.1f} tok/s; "
          f"SLO(2000/50)={m.slo_attainment(2000, 50)*100:.1f}%; "
          f"effective={m.effective_throughput(2000, 50):.1f} tok/s/chip")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llava-next-mistral-7b")
    ap.add_argument("--layers", type=int, default=0,
                    help="0: reduced CPU-sized model; N: published widths "
                         "cut to N layers, bf16, the chip deployment")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--kv-scheme", default="grouped",
                    choices=["one_shot", "layer_wise", "grouped"])
    ap.add_argument("--simulate", action="store_true")
    ap.add_argument("--deployment", default="E-P-D")
    ap.add_argument("--rate", type=float, default=6.0)
    ap.add_argument("--per-chip-rate", action="store_true")
    ap.add_argument("--dataset", default="sharegpt4o",
                    choices=["sharegpt4o", "visualweb"])
    args = ap.parse_args()
    if args.simulate:
        args.requests = max(args.requests, 256)
        run_sim(args)
    else:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        run_real(args)


if __name__ == "__main__":
    main()
