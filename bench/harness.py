"""One run of one cell: set-up, the measured window, the check, the
result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the model as it is run, its serving
  layout and the limits of its correctness check;
* ``bench/traffic/<mix>.json``: the parameters of a traffic mix, read by
  the generator ``bench/traffic/<kind>.py``;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``.

The window drives ``EdgeCluster.submit`` and ``EdgeCluster.step`` (the
calls ``EdgeCluster.run`` makes) from this module's own loop, so that it
ends on time and a closed loop can submit as requests complete.  Every
request is timed from its due time.
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import peaks, trace_reduce

BENCH_DIR = pathlib.Path(__file__).resolve().parent
# the traced slice: the last ``trace_s`` of the window (default below; a
# mix with sparse traffic asks for more).  A TPU trace holds about a
# million device events a second, and writing it out stalls the loop for
# tens of seconds, so the slice is short and the stall falls after the
# window, where it delays only the drain (which it extends).
TRACE_S = 0.5


class Refused(Exception):
    """The run cannot produce a result (no chip, bad cell): exit non-zero
    with no result line."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise Refused(f"missing file {path}") from None


def load_module(path: pathlib.Path):
    """Import a file by path (metric and traffic names may hold dots)."""
    if not path.is_file():
        raise Refused(f"missing file {path}")
    name = "bench_dyn_" + "".join(ch if ch.isalnum() else "_"
                                  for ch in str(path.relative_to(BENCH_DIR)))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def resolve(root: pathlib.Path, workload: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json; have "
                      f"{sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(root / cfg["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")

    def mine(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if mine(m) and m["moves"] in reported]
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer)


def generator(kind: str):
    return load_module(BENCH_DIR / "traffic" / f"{kind}.py")


def reader(metric: str):
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py")


# ---------------------------------------------------------------------------
# seeds, the program's configuration, the chip
# ---------------------------------------------------------------------------


def model_seed(seed: int) -> int:
    """A 31-bit seed for the program's weights from any whole number
    (``jax.random.key`` keeps only 32 bits of what it is given)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


_FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "rope_theta": "rope_theta",
           "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype"}


# what the program does one way only, whatever its ModelConfig says
_FIXED = {"norm_type": "rmsnorm", "use_bias": False}


def config_mismatches(cfg, c: dict) -> List[str]:
    """Where the program's ModelConfig differs from the configuration
    file, so that the file always states what is run; and every key of
    the file's ``departures`` is one compared here, at a value other than
    the published one."""
    want = {v: c[k] for k, v in _FIELDS.items()}
    want["norm_eps"] = c.get("rms_norm_eps", c.get("norm_epsilon"))
    want["gated_ffn"] = c["hidden_act"] == "silu"
    want["qkv_bias"] = bool(c.get("qkv_bias", False))
    bad = [f"{k}: program {getattr(cfg, k)!r}, file {v!r}"
           for k, v in want.items() if getattr(cfg, k) != v]
    bad += [f"{k}: program {v!r}, file {c.get(k)!r}"
            for k, v in _FIXED.items() if c.get(k, v) != v]
    if "norm_type" not in c:
        bad.append("norm_type: not stated")
    window = c.get("sliding_window")
    if cfg.sliding_window != window and not (
            cfg.sliding_window is None and window is not None
            and c["serving"]["max_len"] <= window):
        bad.append(f"sliding_window: program {cfg.sliding_window!r}, "
                   f"file {window!r}")
    compared = set(_FIELDS) | set(_FIXED) | {
        "rms_norm_eps", "norm_epsilon", "hidden_act", "qkv_bias"}
    for k, d in c.get("departures", {}).items():
        if k not in compared:
            bad.append(f"{k}: a departure that is not compared")
        elif c.get(k) == d["published"]:
            bad.append(f"{k}: runs the published {d['published']!r}, "
                       f"no departure")
    return bad


def devices(chips: int):
    """The chips the cell runs on; no TPU, or too few chips, refuses."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs


def peak_bytes() -> int:
    import jax

    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def _warm_token_batches(engine) -> None:
    """On a mesh, the engine stacks each decode round's input tokens from
    host arrays and, for lanes whose prompt finished this step, device
    arrays placed on the mesh; every pattern of the two compiles its own
    concatenation.  A long-running server has compiled the common ones;
    compile here every pattern with at most two device tokens."""
    import itertools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    host = np.zeros((1,), np.int32)
    dev = jax.device_put(host, NamedSharding(engine.mesh, PartitionSpec()))
    L = engine.max_lanes
    for k in (0, 1, 2):
        for pat in itertools.combinations(range(L), k):
            rows = [dev if i in pat else host for i in range(L)]
            jnp.stack([jnp.asarray(r, jnp.int32) for r in rows])


def build(cell: Cell, seed: int):
    """The cell's engines, scheduler and cluster, warmed on the shapes
    its traffic uses."""
    import jax
    import jax.numpy as jnp

    from repro.cluster import EdgeCluster
    from repro.launch.mesh import make_production_mesh
    from repro.launch.serve import build_scheduler
    from repro.serving.builders import (build_engines, build_sharded_engine,
                                        warmup)

    sv = cell.config["serving"]
    common = dict(max_lanes=sv["max_lanes"], page_size=sv["page_size"],
                  prefill_chunk=sv["prefill_chunk"],
                  prefix_cache=sv["prefix_cache"], full_scale=True)
    ms = model_seed(seed)
    if sv["mesh"]:
        mesh = make_production_mesh(shape=tuple(sv["mesh"]),
                                    axes=("data", "model"))
        engines = [build_sharded_engine(cell.config["arch"], sv["max_len"],
                                        mesh=mesh, kv_slots=sv["kv_slots"],
                                        seed=ms, **common)]
    else:
        engines = build_engines(cell.config["arch"], sv["engines"],
                                sv["max_len"], num_pages=sv["num_pages"],
                                seed0=ms, **common)
    jax.block_until_ready([e.params for e in engines])
    bad = config_mismatches(engines[0].cfg, cell.config)
    if bad or engines[0].num_pages != sv["num_pages"] or \
            len(engines) != sv["engines"]:
        raise Refused(f"the program does not run {cell.config['name']} as "
                      f"its file states: {bad}, {engines[0].num_pages} "
                      f"pages, {len(engines)} engines")
    # three tokens: the second decode round feeds host tokens, the first
    # a device one, and on a mesh the two compile apart
    warmup(engines, 2 * sv["prefill_chunk"], gen_tokens=3)
    if sv["mesh"]:
        _warm_token_batches(engines[0])
    scheduler = build_scheduler(sv["scheduler"], len(engines),
                                sv.get("train_episodes", 0),
                                sv.get("policy_seed", 0))
    # the scheduler's own randomness (LAD-TS samples its action) is part
    # of the policy, like its weights: fixed, so that every seed routes
    # the same traffic the same way
    cluster = EdgeCluster(engines, scheduler, seed=sv.get("policy_seed", 0))
    # compile what the first submit would: the scheduler's select and the
    # cluster's key split (the carry is discarded)
    scheduler.select_one(cluster.carry,
                         jnp.zeros((cluster.obs_dim,), jnp.float32), 0, 0,
                         jax.random.key(0))
    jax.random.split(jax.random.key(0))
    return engines, cluster


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunRecord:
    """What a run measured; the metric readers take their numbers here."""

    cell: Cell
    seed: int
    seconds: float
    setup_s: float
    chips: int
    peaks: dict
    t_open: float                       # window opens (host clock)
    t_close: float
    requests: list                      # every Request sent
    due: Dict[int, float]               # rid -> due time (host clock)
    window_tokens: int = 0              # output tokens produced in window
    window_tokens_s: float = 0.0        # the interval they were counted in
    submits: List[tuple] = dataclasses.field(default_factory=list)
    steps: List[tuple] = dataclasses.field(default_factory=list)
    late_s: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0                   # backend compiles in the window
    trace: object = None                # trace_reduce.Trace
    trace_span: tuple = ()              # host clock of the traced slice
    dispatches: List[dict] = dataclasses.field(default_factory=list)
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)

    def in_window(self) -> list:
        """Requests due in the window (open loop), or finished in it
        (closed loop)."""
        if self.cell.traffic["loop"] == "open":
            return [r for r in self.requests
                    if self.t_open <= self.due[r.rid] < self.t_close]
        return [r for r in self.requests if r.status == "ok"
                and self.t_open <= r.t_finish <= self.t_close]


class _Tracer:
    """Profiles one slice of the window; puts ``bench.*`` spans around
    the cluster's calls into each layer."""

    def __init__(self, out_dir: pathlib.Path, engines, record: RunRecord):
        import jax

        self.jax, self.dir, self.rec = jax, out_dir, record
        self.active = False
        self._slice = None
        shutil.rmtree(out_dir, ignore_errors=True)
        out_dir.mkdir(parents=True)
        for i, e in enumerate(engines):
            e.dispatch = self._wrap_dispatch(i, e, e.dispatch)
            e.collect = self._span(f"bench.collect.e{i}", e.collect)

    def _span(self, name: str, fn: Callable) -> Callable:
        ann = self.jax.profiler.TraceAnnotation

        def wrapped(*a, **k):
            with ann(name):
                return fn(*a, **k)
        return wrapped

    def _wrap_dispatch(self, i: int, eng, fn: Callable) -> Callable:
        inner = self._span(f"bench.dispatch.e{i}", fn)

        def dispatch():
            if not self.active:
                return inner()
            before = {id(ln): ln.chunk_pos for ln in eng._lanes
                      if ln is not None}
            t = time.monotonic()
            out = inner()
            prefill = []
            for ln in eng._lanes:
                if ln is None:
                    continue
                c0 = before.get(id(ln), ln.req.prefix_tokens)
                n = min(ln.chunk_pos, ln.prompt_len) - min(c0, ln.prompt_len)
                if n > 0:
                    prefill.append((c0, n))
            decode = []
            if eng._pending is not None and eng._pending.decode is not None:
                decode = [int(req.prompt.shape[-1]) + pos
                          for _, req, pos, _ in eng._pending.decode[1]]
            self.rec.dispatches.append({"t": t, "engine": i,
                                        "prefill": prefill,
                                        "decode": decode})
            return out
        return dispatch

    def start(self) -> None:
        self.jax.profiler.start_trace(str(self.dir))
        self._slice = self.jax.profiler.TraceAnnotation("bench.slice")
        self._slice.__enter__()
        self.t0 = time.monotonic()
        self.active = True

    def stop(self) -> None:
        self.active = False
        t1 = time.monotonic()
        self._slice.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.rec.trace_span = (self.t0, t1)


def _served_tokens(requests) -> int:
    return sum(len(r.tokens) for r in requests)


def drive(cluster, engines, plan, record: RunRecord,
          tracer: Optional[_Tracer], compiles: Callable[[], int]) -> None:
    """Offer the plan's traffic to the cluster: lead-in, window, and for
    an open loop the drain.  Fills ``record``; ``compiles`` counts the
    process's backend compiles so far."""
    from repro.cluster import Request

    import jax

    clock = time.monotonic
    seconds = record.seconds
    ann = (jax.profiler.TraceAnnotation if tracer is not None
           else None)
    t_open = clock() + plan.lead_in_s
    t_close = t_open + seconds
    record.t_open, record.t_close = t_open, t_close
    t_trace = (t_close - record.cell.traffic.get("trace_s", TRACE_S)
               if tracer is not None else math.inf)
    drain_end = t_close + plan.drain_s

    open_loop = plan.loop == "open"
    if open_loop:
        todo = [(t_open + it.due_s, it.rid, it) for it in plan.items]
        heapq.heapify(todo)
        queues = None
    else:
        queues = [list(reversed(q)) for q in plan.client_queues()]
        todo = [(t_open - plan.lead_in_s, c, queues[c].pop())
                for c in range(plan.clients) if queues[c]]
        heapq.heapify(todo)
    client_of = {}
    counted0 = None

    def submit(due: float, it, now: float) -> None:
        req = Request(rid=it.rid, prompt=it.prompt,
                      max_new_tokens=it.max_new_tokens, origin=it.origin)
        req.t_arrival = due
        record.requests.append(req)
        record.due[it.rid] = due
        client_of[it.rid] = it.client
        if due >= t_open:
            record.late_s.append(now - due)
        t = clock()
        if ann is not None:
            with ann("bench.submit"):
                cluster.submit(req)
        else:
            cluster.submit(req)
        if t_open <= t < t_close:
            record.submits.append((t, clock() - t))

    while True:
        now = clock()
        if counted0 is None and now >= t_open:
            counted0 = (now, _served_tokens(record.requests), compiles())
        if tracer is not None:
            if not tracer.active and now >= t_trace:
                tracer.start()
                t_trace = math.inf
            elif tracer.active and now >= t_close:
                tracer.stop()
                drain_end += clock() - now
        while todo and todo[0][0] <= now and todo[0][0] < t_close:
            due, _, it = heapq.heappop(todo)
            submit(due, it, now)
        if now >= t_close:
            if not record.window_tokens_s:
                record.window_tokens = (_served_tokens(record.requests)
                                        - counted0[1])
                record.window_tokens_s = now - counted0[0]
                record.compiles = compiles() - counted0[2]
            if not open_loop or not cluster.busy or now >= drain_end:
                break
        if cluster.busy:
            t = clock()
            if ann is not None:
                with ann("bench.step"):
                    done = cluster.step()
            else:
                done = cluster.step()
            t1 = clock()
            record.steps.append((t, t1 - t))
            if not open_loop:
                for r in done:
                    c = client_of.get(r.rid)
                    if c is not None and queues[c]:
                        nxt = queues[c].pop()
                        heapq.heappush(todo, (t1 + nxt.think_s, c, nxt))
        else:
            wait = todo[0][0] - now if todo else t_close - now
            time.sleep(min(0.002, max(wait, 0.0)))
    if tracer is not None and tracer.active:
        tracer.stop()


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def _served(r) -> np.ndarray:
    return np.concatenate([np.ravel(np.asarray(t)) for t in r.tokens]
                          ).astype(np.int32)


def sample(requests, seed: int, page_size: int, budget: int,
           most: int) -> list:
    """Finished requests to compare, drawn from the seed: the longest,
    one that forked a cached page copy-on-write and one that reused
    cached pages (where there are such), then others in the seed's order
    until ``budget`` served tokens or ``most`` requests."""
    done = [r for r in requests if r.status == "ok"]
    if not done:
        return []
    order = [done[i] for i in
             np.random.default_rng([seed, 7]).permutation(len(done))]
    picks = [max(done, key=lambda r: (r.prompt.shape[-1] + len(r.tokens),
                                      r.rid))]
    for want in (lambda r: r.prefix_tokens % page_size != 0,
                 lambda r: r.prefix_tokens > 0):
        hit = next((r for r in order if want(r)), None)
        if hit is not None and hit not in picks:
            picks.append(hit)
    for r in order:
        if (sum(len(p.tokens) for p in picks) >= budget
                or len(picks) >= most):
            break
        if r not in picks:
            picks.append(r)
    return picks


def compare(cell: Cell, record: RunRecord, control: bool = False) -> dict:
    """Run the plain reference over the sampled requests' prompts and
    served tokens; the widest gap is the number compared."""
    ref = load_module(BENCH_DIR / "reference"
                      / f"{cell.config['reference']}.py")
    sv, chk = cell.config["serving"], cell.config["check"]
    picks = sample(record.requests, record.seed, sv["page_size"],
                   chk["sample_tokens"], chk["max_sequences"])
    ms = model_seed(record.seed)
    rows = int(cell.traffic["output"]["max"])
    gaps, ctl, hits, forks = [], [], 0, 0
    by_engine: Dict[int, list] = {}
    for r in picks:
        by_engine.setdefault(r.engine_id, []).append(r)
        hits += r.prefix_tokens > 0
        forks += r.prefix_tokens % sv["page_size"] != 0
    for eng, rs in sorted(by_engine.items()):
        seqs = [(np.ravel(np.asarray(r.prompt)).astype(np.int32), _served(r))
                for r in rs]
        out = ref.served_gaps(cell.config, ms + eng, seqs, sv["max_len"],
                              rows, control=control)
        gaps += [float(g.max()) for g in out["served"]]
        ctl += [float(g.max()) for g in out.get("control", [])]
    return {"max_logit_gap": max(gaps) if gaps else None,
            "control_max_logit_gap": max(ctl) if ctl else None,
            "sequences": len(picks),
            "tokens": sum(len(r.tokens) for r in picks),
            "prefix_hits": hits, "cow_forks": forks}


def checks(cell: Cell, record: RunRecord, cmp: dict) -> Dict[str, dict]:
    """Each number compared, beside its limit."""
    vocab = cell.config["vocab_size"]
    bad_tokens = sum(
        int(r.status == "ok" and (len(r.tokens) != r.max_new_tokens or not (
            (_served(r) >= 0) & (_served(r) < vocab)).all()))
        for r in record.requests)
    return {
        "max_logit_gap": {"value": cmp["max_logit_gap"],
                          "limit": cell.config["check"]["max_logit_gap"]},
        "compared_tokens": {"value": cmp["tokens"], "limit": 1},
        "malformed_outputs": {"value": bad_tokens, "limit": 0},
        "quarantined": {"value": record.counters.get("quarantined", 0),
                        "limit": 0},
    }


def is_correct(c: Dict[str, dict]) -> bool:
    gap = c["max_logit_gap"]
    return (gap["value"] is not None and gap["value"] <= gap["limit"]
            and c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"]
            and c["malformed_outputs"]["value"] <= 0
            and c["quarantined"]["value"] <= 0)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        trace: bool, t_process: float, rate: Optional[float] = None,
        control: bool = False, mix: Optional[str] = None) -> dict:
    """One run of one cell; returns the result object (the last line).

    For ``bench/sweep.py`` and ``bench/calibrate.py`` only: ``rate``
    overrides an open-loop mix's request rate, ``mix`` serves another
    traffic file, and ``control`` also runs the float8 control over the
    compared requests and judges it by the same checks
    (``control_correct``)."""
    cell = resolve(root, workload)
    if mix is not None:
        cell.traffic = _load_json(root / "bench" / "traffic" / f"{mix}.json")
    if rate is not None:
        cell.traffic["request_rate_per_s"] = rate

    from repro.launch.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    import jax
    from jax import monitoring

    devs = devices(cell.chips)
    dev = devs[0]
    pk = peaks.peaks(dev.device_kind)
    compiles = [0]

    def on_event(event: str, *_a, **_k) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    monitoring.register_event_duration_secs_listener(on_event)

    engines, cluster = build(cell, seed)
    gen = generator(cell.traffic["kind"])
    plan = gen.generate(cell.traffic, seed, seconds,
                        cell.config["vocab_size"], len(engines))
    record = RunRecord(cell=cell, seed=seed, seconds=seconds, setup_s=0.0,
                       chips=cell.chips, peaks=pk, t_open=0.0, t_close=0.0,
                       requests=[], due={})
    tracer = (_Tracer(root / "bench_out" / "trace" / workload, engines,
                      record) if trace else None)
    record.setup_s = time.perf_counter() - t_process
    _log(f"set-up {record.setup_s:.3f} s; {len(plan.items)} requests "
         f"planned over {plan.lead_in_s} s lead-in + {seconds} s window")
    drive(cluster, engines, plan, record, tracer, lambda: compiles[0])
    record.counters = {
        "quarantined": cluster.fault_stats["quarantined"],
        "prefix_hits": sum(e.prefix_hits for e in engines),
        "prefix_lookups": sum(e.prefix_lookups for e in engines),
        "cow_forks": sum(e.cow_forks for e in engines)}
    memory = peak_bytes()
    late = np.asarray(record.late_s) * 1e3
    _log(f"window: {len(record.in_window())} requests, "
         f"{len(record.steps)} cluster steps, compiles in the window "
         f"{record.compiles}; submit lateness ms p50 "
         f"{np.percentile(late, 50) if late.size else 0:.3f} p95 "
         f"{np.percentile(late, 95) if late.size else 0:.3f} max "
         f"{late.max() if late.size else 0:.3f}; counters "
         f"{record.counters}")

    _log(f"load {json.dumps(load_summary(record))}")
    if trace:
        record.trace = trace_reduce.load(trace_reduce.find_xplane(
            str(tracer.dir)))
    # the program's state goes before the reference runs
    del cluster, engines, tracer
    gc.collect()
    t = time.perf_counter()
    cmp = compare(cell, record, control=control)
    _log(f"reference over {cmp['sequences']} requests, {cmp['tokens']} "
         f"tokens ({cmp['prefix_hits']} with cached pages, "
         f"{cmp['cow_forks']} forked): {time.perf_counter() - t:.3f} s")

    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"]).read(record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = _failed(record)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    result = {"correct": False, "attempted": len(_attempted(record)),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_reduce.busy_s(record.trace)
        device["window_s"] = record.trace.window_s
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(record.trace),
            "idle_gaps": trace_reduce.idle_gaps(record.trace)}
    chk = checks(cell, record, cmp)
    result["correct"] = is_correct(chk)
    if control:
        # the same comparison, with the control's gaps in the program's
        ctl = dict(cmp, max_logit_gap=cmp["control_max_logit_gap"])
        result["control_correct"] = is_correct(checks(cell, record, ctl))
        result["control_max_logit_gap"] = cmp["control_max_logit_gap"]
    result["checks"] = chk
    for name, c in chk.items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def load_summary(record: RunRecord) -> dict:
    """Offered against delivered output tokens/s, and the median TTFT of
    the window's first and last thirds (a growing queue shows as a
    rising TTFT): what ``bench/sweep.py`` reads to find the knee."""
    reqs = record.in_window()
    thirds = [[], [], []]
    for r in reqs:
        if r.status == "ok":
            k = int(3 * (record.due[r.rid] - record.t_open) / record.seconds)
            thirds[min(max(k, 0), 2)].append(r.t_prefill_end - r.t_arrival)
    return {
        "offered_tokens_per_s": sum(r.max_new_tokens for r in reqs)
        / record.seconds,
        "delivered_tokens_per_s": (record.window_tokens
                                   / record.window_tokens_s
                                   if record.window_tokens_s else 0.0),
        "ttft_p50_ms_by_third": [float(np.median(t)) * 1e3 if t else None
                                 for t in thirds],
        "requests": len(reqs), "failed": _failed(record)}


def _attempted(record: RunRecord) -> list:
    if record.cell.traffic["loop"] == "open":
        return record.in_window()
    return record.in_window() + [r for r in record.requests
                                 if r.status in ("failed", "abandoned")]


def _failed(record: RunRecord) -> int:
    return sum(r.status != "ok" for r in _attempted(record))
