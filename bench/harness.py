"""One cell, once: build the served system, warm it up, measure a window,
check what it served against the reference.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json`` (whose ``generator``
names ``bench/traffic/gen_<generator>.py``), each per-layer metric in
``bench/metrics/<metric>.py`` (end-to-end and per-layer alike: each
reads a :class:`Context`) and the chip's peaks in ``bench/peaks.json``.
Adding a cell, a configuration, a mix or a metric adds files and edits
none.

The system under test is ``repro.core.cluster.EPDCluster`` driven through
``run_continuous``: each burst is one call, submitted at once, and the
next starts when it has drained. ``on_step`` runs after every iteration
of the serving loop and stamps each request's new tokens with the host
clock, which is when a streaming client would see them (a token exists
on the host only after the step's device-to-host copy).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import logging
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

from bench import check, reference, stats, trace_reduce, work  # noqa: E402

# a program lowered to MLIR: a new shape, compiled or read from the cache
_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


# ---- finding things by name ------------------------------------------------

def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(name: str, root: Path = REPO) -> Cell:
    doc = read_json(root / "BENCHMARK.json")
    wl = _named(doc["workloads"], name, "workload")
    cfg = read_json(root / _named(doc["configs"], wl["config"],
                                  "configuration")["file"])
    traffic = read_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")

    def reports(m):
        return name in m.get("workloads", [name])

    return Cell(wl, cfg, traffic,
                [m for m in doc["end_to_end"] if reports(m)],
                [m for m in doc["per_layer"] if reports(m)])


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench._" + "".join(c if c.isalnum() else "_" for c in
                               str(path.relative_to(BENCH).with_suffix("")))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def generator(traffic: dict):
    return load_module(BENCH / "traffic" / f"gen_{traffic['generator']}.py")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py")


def peaks(device_kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


# ---- the model -------------------------------------------------------------

def model_config(doc: dict):
    """The registry's ModelConfig with every size of the file applied."""
    from repro.configs import get_config
    m = doc["model"]
    base = get_config(doc["registry"])
    kw = dict(n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
              n_heads=m["num_attention_heads"],
              n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
              d_ff=m["intermediate_size"], vocab=m["vocab_size"],
              rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
              tie_embeddings=m["tie_word_embeddings"])
    if "image_tokens" in m:
        kw["frontend"] = dataclasses.replace(
            base.frontend, tokens_per_item=m["image_tokens"],
            feature_dim=m["vision_feature_dim"])
    elif base.frontend is not None:
        raise ValueError(f"{doc['registry']} has an image frontend; the "
                         f"configuration must give its sizes")
    return dataclasses.replace(base, **kw)


def dims(doc: dict) -> work.Dims:
    m = doc["model"]
    return work.Dims(m["num_hidden_layers"], m["hidden_size"],
                     m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"], m["intermediate_size"], m["vocab_size"],
                     m.get("vision_feature_dim", 0))


def seed_key(seed: int, stream: int):
    import jax
    a, b = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) & 0x7FFFFFFF),
                              int(b) & 0x7FFFFFFF)


def make_weights(cfg, seed: int, dtype):
    """Every weight from ``seed`` in one jitted call, on the device, in
    the served dtype: normal with std 1/sqrt(fan_in), norms 1."""
    import jax
    import jax.numpy as jnp
    from repro.models.params import ParamSpec, param_structure

    leaves, tree = jax.tree.flatten(
        param_structure(cfg), is_leaf=lambda x: isinstance(x, ParamSpec))

    def one(spec, k):
        if spec.init in ("ones", "zeros"):
            fill = 1.0 if spec.init == "ones" else 0.0
            return jnp.full(spec.shape, fill, dtype)
        if spec.init != "normal":
            raise ValueError(f"no weight rule for init {spec.init!r}")
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        return (jax.random.normal(k, spec.shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [one(s, keys[i])
                                         for i, s in enumerate(leaves)])

    return make(seed_key(seed, 7))


# ---- the serving window ----------------------------------------------------

class Recorder:
    """The ``on_step`` hook: host-clock token times, decode occupancy and
    the key lengths of every decode step (for the work counts)."""

    def __init__(self, window: stats.Window, max_batch: int):
        self.window = window
        self.max_batch = max_batch
        self.live: List[list] = []         # [request, times, tokens seen]
        self.decode_steps = 0
        self.occupancy = 0.0
        self.lengths: Optional[List[List[int]]] = None   # set to record
        self.then: Optional[Callable[[float], None]] = None

    def begin(self, reqs, t_submit: float) -> None:
        self.live = []
        for r in reqs:
            rt = stats.RequestTimes(t_submit)
            self.window.requests[r.request_id] = rt
            self.live.append([r, rt, 0])

    def __call__(self, _step: int) -> None:
        now = time.perf_counter()
        decoded = []
        for item in self.live:
            r, rt, seen = item
            n = len(r.output_tokens)
            if n == seen:
                continue
            rt.tokens.extend([now] * (n - seen))
            # the first token comes with admission, later ones from
            # decode steps: a request that went 0 -> 1 was only admitted
            if seen or n >= 2:
                decoded.append(r.total_prompt_len + n - 1)
            item[2] = n
        if decoded:
            self.decode_steps += 1
            self.occupancy += len(decoded) / self.max_batch
            if self.lengths is not None:
                self.lengths.append(decoded)
        if self.then is not None:
            self.then(now)


def to_request(spec, image_tokens: int):
    from repro.serving.request import Request
    return Request(prompt_tokens=list(spec.prompt),
                   max_new_tokens=spec.max_new, mm_payload=spec.image,
                   eos_token=-1,
                   mm_tokens=image_tokens if spec.image else 0,
                   mm_pos=spec.image_pos)


def warm_eager(cluster) -> None:
    """Eager ops whose shapes depend on the step: the decode engine's
    block-table growth (one index per slot that crosses a page) and the
    slot release (one per slot index)."""
    import jax
    import jax.numpy as jnp
    for eng in cluster.decode_engines:
        pages = eng.caches["pages"]
        for n in range(1, eng.max_batch + 1):
            idx = list(range(n))
            jax.block_until_ready(pages.at[idx, [0] * n].set(
                jnp.asarray([0] * n, jnp.int32)))
        for slot in range(eng.max_batch):
            jax.block_until_ready(pages.at[slot].set(0))


class CompileWatch:
    """Counts the programs lowered while ``on`` (each new shape, whether
    compiled or read from the persistent cache), with the names JAX logs
    for them. Only the logger that names a compilation is opened, to a
    handler of its own: nothing is printed per dispatch."""

    _LOGGER = "jax._src.interpreters.pxla"

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._event)
        watch = self

        class _Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if watch.on and msg.startswith("Compiling"):
                    watch.names.append(msg[:160])

        self._handler = _Names(logging.DEBUG)
        self._saved = None

    def _event(self, event, _duration, **_):
        if self.on and event == _LOWERED:
            self.count += 1

    def start(self):
        log = logging.getLogger(self._LOGGER)
        self._saved = (log.level, log.propagate)
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self._handler)
        self.on = True

    def stop(self):
        self.on = False
        log = logging.getLogger(self._LOGGER)
        log.removeHandler(self._handler)
        log.level, log.propagate = self._saved


def _served(reqs, done_ids, image_tokens) -> List[check.Served]:
    return [check.Served(r.prompt_tokens, r.mm_payload, r.mm_pos,
                         image_tokens, list(r.output_tokens))
            for r in reqs if r.request_id in done_ids]


class Traced:
    """The profiled part of a ``--trace 1`` window: starts at the first
    burst submit or serving iteration ``after`` seconds into the window
    (0: with the first burst, its encodes included) and stops at the
    first iteration ``length`` seconds later, each time after the device
    has finished what was queued, and snapshots the counters both
    times."""

    def __init__(self, cluster, rec: Recorder, out: Path, after: float,
                 length: float):
        import jax
        import jax.numpy as jnp
        self.cluster, self.rec, self.out = cluster, rec, out
        self.after, self.length = after, length
        self.t0 = self.t1 = None
        self.c0 = self.c1 = None
        self.gauges: Dict[str, float] = {}
        self.lengths: Optional[List[List[int]]] = None
        self._sync = jax.jit(lambda x: x + 1)
        jax.block_until_ready(self._sync(jnp.zeros((), jnp.int32)))

    def counters(self) -> Dict[str, float]:
        """Every counter of the cluster's registry, summed over its
        labels, and the decode steps and spans so far."""
        return {**registry_totals(self.cluster.metrics, "counters"),
                "bench.decode_steps": self.rec.decode_steps,
                "bench.spans": len(self.cluster.tracer.spans)}

    def _quiet(self):
        import jax
        import jax.numpy as jnp
        jax.block_until_ready(self._sync(jnp.zeros((), jnp.int32)))

    def __call__(self, now: float) -> None:
        import jax
        if self.t0 is None:
            if now - self.rec.window.start >= self.after:
                self._quiet()
                shutil.rmtree(self.out, ignore_errors=True)
                jax.profiler.start_trace(str(self.out))
                self.c0 = self.counters()
                self.rec.lengths = []
                self.t0 = time.perf_counter()
        elif self.t1 is None and now - self.t0 >= self.length:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.t0 is None or self.t1 is not None:
            return
        self._quiet()
        self.t1 = time.perf_counter()
        self.c1 = self.counters()
        self.gauges = registry_totals(self.cluster.metrics, "gauges")
        self.lengths, self.rec.lengths = self.rec.lengths, None
        self.rec.then = None
        jax.profiler.stop_trace()


def _chunks(spans, requests_prompt: Dict[int, int], lo: int, hi: int):
    """(start, end, last) of every prefill chunk computed among
    spans[lo:hi]. A ``prefill.chunk`` span is also written for an
    attempt that stalled on a full page pool and computed nothing: the
    same chunk index then comes again, and only its last span counts.
    A restarted prefill (pool deadlock recovery) begins again at chunk
    0. A request's chunks follow each other from where the prefix cache
    left off (its prompt less what its last run of chunks computed) to
    its prompt length."""
    runs: Dict[int, List[List[list]]] = {}       # rid -> runs of [k, n, i]
    for i, s in enumerate(spans):
        if s.name != "prefill.chunk" or s.request_id not in requests_prompt:
            continue
        k, n = int(s.attrs["chunk"]), int(s.attrs["tokens"])
        mine = runs.setdefault(s.request_id, [[]])
        cur = mine[-1]
        if cur and cur[-1][0] == k:
            cur[-1] = [k, n, i]                    # the stalled try again
        elif cur and k == 0:
            mine.append([[k, n, i]])               # a restart
        else:
            cur.append([k, n, i])
    out = []
    for rid, mine in runs.items():
        n_prompt = requests_prompt[rid]
        first = n_prompt - sum(n for _, n, _ in mine[-1])
        for cur in mine:
            a = first
            for _, n, i in cur:
                if lo <= i < hi:
                    out.append((a, a + n, a + n == n_prompt))
                a += n
    return out


def registry_totals(registry, kind: str) -> Dict[str, float]:
    """``{name: value}`` of every metric of ``kind`` (``"counters"`` or
    ``"gauges"``) in a ``MetricsRegistry``, labels summed."""
    out: Dict[str, float] = {}
    for key, v in registry.snapshot()[kind].items():
        name = key.split("{", 1)[0]
        out[name] = out.get(name, 0.0) + v
    return out


@dataclasses.dataclass
class Context:
    """What a metric's reader (``bench/metrics/<name>.py``, ``read(ctx)``)
    reads. End-to-end readers read the window and the set-up time;
    per-layer readers the rest, which a ``--trace 1`` run fills in for
    the traced part of the window (``None`` otherwise). A reader that
    finds nothing to read returns ``None``."""

    window: stats.Window                  # every request of the window
    setup_s: float
    decode_steps: int = 0                 # whole window
    occupancy: Optional[float] = None     # active slots / slots, mean
    dims: Optional[work.Dims] = None
    peaks: Optional[dict] = None
    image_tokens: int = 0
    window_s: Optional[float] = None      # traced part, host clock
    trace: Optional[trace_reduce.Reduced] = None
    events: Optional[List[trace_reduce.Event]] = None   # attached
    counts: Optional[Dict[str, float]] = None   # counters, traced part
    gauges: Optional[Dict[str, float]] = None   # at its end
    chunks: Optional[List[tuple]] = None  # (start, end, last) per chunk
    decode_lengths: Optional[List[List[int]]] = None   # per decode step

    def program_s(self, *programs: str) -> float:
        """Device seconds of these programs (``jit_decode_fn``, ...)."""
        return sum(self.trace.programs.get(p, 0.0) for p in programs)

    def op_s(self, program: str, kind: str) -> float:
        """Device seconds of the ops of one kind inside one program, e.g.
        ``("jit_decode_fn", "custom-call:tpu_custom_call")``."""
        return self.trace.by_kind.get(f"{program}/{kind}", 0.0)


def trace_context(ctx: Context, cell: Cell, tr: Traced,
                  events: List[trace_reduce.Event],
                  red: trace_reduce.Reduced, reqs_prompt: Dict[int, int],
                  device_kind: str) -> None:
    """Fill in the traced part: the trace, the counts of that part, its
    prefill chunks and decode steps' key lengths."""
    c0, c1 = tr.c0, tr.c1
    ctx.counts = {k: c1[k] - c0.get(k, 0.0) for k in c1}
    ctx.gauges = tr.gauges
    ctx.chunks = _chunks(tr.cluster.tracer.spans, reqs_prompt,
                         c0["bench.spans"], c1["bench.spans"])
    ctx.decode_lengths = tr.lengths or []
    ctx.dims = dims(cell.config)
    ctx.peaks = peaks(device_kind)
    ctx.image_tokens = cell.config["model"].get("image_tokens", 0)
    ctx.window_s = tr.t1 - tr.t0
    ctx.trace, ctx.events = red, events


def read_metrics(metrics: List[dict], ctx: Context,
                 required: bool) -> Dict[str, dict]:
    out = {}
    for m in metrics:
        v = reader(m["name"]).read(ctx)
        if v is None:
            if required:
                raise RuntimeError(f"the window gave no {m['name']}")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, out_dir: Path,
             log: Callable[[str], None] = print) -> dict:
    """Serve the cell for ``seconds`` and return the result line's dict."""
    import jax
    import jax.numpy as jnp
    from repro.core.cluster import EPDCluster
    from repro.core.telemetry import Tracer

    doc, traffic = cell.config, cell.traffic
    serving = doc["serving"]
    cfg = model_config(doc)
    dtype = jnp.dtype(doc["dtype"])
    image_tokens = doc["model"].get("image_tokens", 0)
    gen = generator(traffic)
    sizes = dict(image_tokens=image_tokens, max_len=serving["max_len"])

    params = make_weights(cfg, seed, dtype)
    jax.block_until_ready(params)
    tracer = Tracer(enabled=True, decode_sample=1 << 62) if trace else None
    cluster = EPDCluster(cfg, params, tracer=tracer, **serving)

    # set-up: every program shape the mix produces, then the eager ops
    warm = [to_request(s, image_tokens) for s in gen.warmup(
        traffic, vocab=cfg.vocab, page=serving["page_size"], **sizes)]
    done = cluster.run_continuous(warm)
    if len(done) != len(warm) or cluster.report.lost:
        raise RuntimeError("warm-up requests did not all complete")
    warm_eager(cluster)
    if trace:
        jax.profiler.start_trace(str(out_dir / "warm"))
        jax.profiler.stop_trace()
        shutil.rmtree(out_dir / "warm", ignore_errors=True)

    watch = CompileWatch()
    bursts = gen.bursts(traffic, seed, vocab=cfg.vocab, **sizes)
    # what set-up made stays alive: keep the collector from walking it
    gc.collect()
    gc.freeze()
    window = stats.Window(start=time.perf_counter())
    setup_s = window.start - t_process
    rec = Recorder(window, serving["max_batch"])
    tr = None
    if trace:
        tr = Traced(cluster, rec, out_dir / "trace",
                    traffic["trace_after_s"], traffic["trace_s"])
        rec.then = tr
    watch.start()
    served: List[check.Served] = []
    prompts: Dict[int, int] = {}
    n_bursts = 0
    while n_bursts == 0 or time.perf_counter() - window.start < seconds:
        reqs = [to_request(s, image_tokens) for s in next(bursts)]
        prompts.update((r.request_id, r.total_prompt_len) for r in reqs)
        if tr is not None:
            tr(time.perf_counter())   # a trace may start with the burst
        rec.begin(reqs, time.perf_counter())
        done = cluster.run_continuous(reqs, on_step=rec)
        ids = {r.request_id for r in done}
        for r in reqs:
            if r.request_id not in ids:
                window.requests[r.request_id].failed = True
        served += _served(reqs, ids, image_tokens)
        n_bursts += 1
    watch.stop()
    if tr is not None:
        tr.stop()
    gc.unfreeze()
    lost = len(cluster.report.lost)

    devs = jax.devices()
    mem = [(dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for dv in devs]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(max(mem))}
    log(f"window: {window.seconds!r} s real (asked {seconds} s), "
        f"{n_bursts} bursts, {window.attempted} requests, "
        f"{window.output_tokens} output tokens, {rec.decode_steps} decode "
        f"steps; set-up {setup_s!r} s")
    log(f"compilations inside the window: {watch.count}"
        + "".join(f"\n  {n}" for n in watch.names[:20]))

    result = {"correct": False, "attempted": window.attempted,
              "failed": window.failed, "metrics": {}, "device": device}
    ctx = Context(window, setup_s, rec.decode_steps,
                  rec.occupancy / rec.decode_steps
                  if rec.decode_steps else None)
    if tr is not None:
        if tr.t1 is None:
            raise RuntimeError("the window ended before the traced part "
                               "began: give it more --seconds")
        path = trace_reduce.find_xplane(str(tr.out))
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        events = trace_reduce.load(path)
        red = trace_reduce.reduce(events)
        shutil.rmtree(tr.out, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = tr.t1 - tr.t0
        trace_context(ctx, cell, tr, events, red, prompts, device["kind"])
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
        calls = {k: v for k, v in red.by_kind.items() if "custom-call" in k}
        log(f"trace: programs {json.dumps(red.programs)} runs "
            f"{json.dumps(red.executions)} custom calls {json.dumps(calls)}")
        log(f"traced counts {json.dumps(ctx.counts)}")

    # the reference runs once the window's state is gone
    del cluster, tr, warm, done, rec
    gc.collect()
    arch = reference.Arch.from_doc(doc["model"])
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([int(seed), 2])))
    sample = check.sample(served, rng, traffic["check_tokens"])
    t_ref = time.perf_counter()
    got = check.readings(params, arch, sample, length=serving["max_len"],
                         n_read=int(traffic["output_tokens"]["max"]))
    log(f"reference: {got['requests_compared']} requests, "
        f"{got['tokens_compared']} tokens in {time.perf_counter() - t_ref!r}"
        f" s")
    compared = check.compared(got, doc["check"]["max_logit_gap"],
                              failed=window.failed + lost)
    result["correct"] = check.verdict(compared, got)

    if trace:
        result["metrics"] = read_metrics(cell.per_layer, ctx, required=False)
    else:
        result["metrics"] = read_metrics(cell.end_to_end, ctx, required=True)
    result["check"] = compared
    return result
