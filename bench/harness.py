"""One run of one cell: the served path under a traffic mix, timed from
the clients' side, checked against the plain reference.

The server is this process: it makes the weights on the device from the
seed, builds ``BatchedServer`` with the program's default
``OffloadPolicy`` at the cell's ``max_batch``, serves it over
``serve_over_ipc()``, and spawns the mix's client processes, which never
touch the chip.  Each request then goes client -> ``ShmTransport`` ->
reactor -> ``RequestDispatcher`` batch -> ``AsyncTransferEngine`` ->
``jit(prefill)`` / ``jit(decode)`` -> reply over the fabric.

Set-up (``setup_s``: process start to the first due request) makes the
weights, warms every program the cell's traffic can call (each batch size
from 1 to ``max_batch``) and waits for the clients to connect.  The
window then runs for ``--seconds``; with ``--trace 1`` the program's spans
are on and the JAX profiler records a sub-window of it.  After the window
every reply is awaited, device memory is read, the program's state is
freed, and a seeded sample of what came back to the clients is compared
with the reference.

Each metric has a reader of its own in ``bench/metrics/<name>.py``; each
configuration and mix is a data file found by name.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import multiprocessing as mp
import queue
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field, replace
from pathlib import Path

import numpy as np

from bench import gen, xtrace
from bench.client import GRACE_S, client_main, sleep_until

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_RING_RECORDS = 1 << 17       # per writing thread; drops are counted
#: host spans of the server that can explain a device idle gap
GAP_SPANS = ("serve.generate_batch", "dispatcher.handler",
             "dispatcher.gather", "dispatcher.batch_wait",
             "reactor.reply_fill", "reactor.drain")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the data files
# ---------------------------------------------------------------------------

def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bm: dict, workload: str) -> tuple:
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    return cell, json.loads((ROOT / conf["file"]).read_text())


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bm: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end (trace 0) or per-layer (trace 1) metrics."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# the program's model, checked against the configuration file
# ---------------------------------------------------------------------------

def program_config(conf: dict):
    from repro.configs import get_config
    from repro.models import moe as moe_mod

    cfg = replace(get_config(conf["repo_config"]), **conf["changes"])
    a = conf["arch"]
    got = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
           "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
           "head_dim": cfg.resolved_head_dim(), "d_ff": cfg.d_ff,
           "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
           "tie_embeddings": cfg.tie_embeddings,
           "num_experts": cfg.num_experts,
           "experts_per_token": cfg.num_experts_per_token}
    if cfg.num_experts:
        got.update(capacity_factor=cfg.moe_capacity_factor,
                   moe_group=moe_mod.GROUP_SIZE)
    want = {k: a.get(k, 0) for k in got}
    fixed = {"family": cfg.family in ("dense", "moe"),
             "mlp_type": cfg.mlp_type == "swiglu",
             "norm_type": cfg.norm_type == "rmsnorm",
             "qk_norm": not cfg.qk_norm,
             "attn_logit_softcap": not cfg.attn_logit_softcap,
             "dtype": cfg.dtype == conf["dtype"]
             and cfg.param_dtype == conf["dtype"]}
    bad = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
    bad.update({k: "differs" for k, ok in fixed.items() if not ok})
    if bad:
        raise SystemExit(f"program config {cfg.name} departs from the "
                         f"configuration file: {bad}")
    return cfg


def _check_layout(model, arch, model_mod, dtype) -> None:
    import jax
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(lambda w: model_mod.init_weights(w, arch, dtype),
                         model_mod.seed_words(0))
    sw = {jax.tree_util.keystr(p): (x.shape, x.dtype)
          for p, x in jax.tree_util.tree_leaves_with_path(want)}
    sg = {jax.tree_util.keystr(p): (x.shape, x.dtype)
          for p, x in jax.tree_util.tree_leaves_with_path(got)}
    if sw != sg:
        diff = sorted(set(sw.items()) ^ set(sg.items()))
        raise SystemExit(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")


# ---------------------------------------------------------------------------
# what a run leaves for the metric readers
# ---------------------------------------------------------------------------

@dataclass
class Run:
    workload: str
    seed: int
    seconds: float
    chips: int
    arch: object
    model: object                  # bench.models.<name> module
    mix: dict
    setup_s: float = 0.0
    t0: float = 0.0                # window, perf_counter seconds
    t_end: float = 0.0
    requests: list = field(default_factory=list)
    cpu_s: float = 0.0             # server + clients, over the window
    disp0: dict = field(default_factory=dict)
    disp1: dict = field(default_factory=dict)
    batches: list = field(default_factory=list)  # (t0_ns, t1_ns, B, S)
    spans: object = None           # repro.obs.trace.TraceView
    dtrace: object = None          # xtrace.DeviceTrace
    trace_lo: float = 0.0          # traced sub-window, perf_counter ns
    trace_hi: float = 0.0

    def window_requests(self) -> list:
        return [r for r in self.requests if self.t0 <= r["due"] < self.t_end]

    def completed_in_window(self) -> int:
        return sum(1 for r in self.requests
                   if self.t0 <= r["done"] <= self.t_end)

    def span_records(self, name: str, lo_ns: float, hi_ns: float):
        """Spans of one kind that started inside ``[lo_ns, hi_ns]``."""
        from repro.obs import trace as rtrace
        kind = {v: k for k, v in rtrace.KIND_NAMES.items()}[name]
        recs = self.spans.records_of(kind)
        t0 = recs["t0"].astype(np.float64)
        return recs[(t0 >= lo_ns) & (t0 <= hi_ns)]

    def complete_batches(self) -> list:
        """Step programs of each batch that ran wholly inside the traced
        window: ``[(B, S, [(program, t0, t1), ...]), ...]``."""
        if self.dtrace is None:
            return []
        mods = [m for evs in self.dtrace.modules.values() for m in evs
                if "prefill" in m[0] or "decode" in m[0]]
        out = []
        for b0, b1, bsz, seq in self.batches:
            if b0 < self.trace_lo or b1 > self.trace_hi:
                continue
            calls = [(xtrace.program_name(n), t0, t1) for n, t0, t1 in mods
                     if b0 <= t0 <= b1]
            if calls:
                out.append((bsz, seq, sorted(calls, key=lambda c: c[1])))
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             platform: str = "tpu", t_start: float | None = None,
             config: dict | None = None, mix: dict | None = None,
             fault: str | None = None,
             control: bool = False, rate: float | None = None) -> dict:
    """Run one cell once and return its result line.

    The chip command always passes ``platform="tpu"``.  ``config``,
    ``mix`` and ``fault`` serve the CPU tests (a smoke-sized
    configuration, a shorter check sample, a planted fault).
    ``control`` puts the lower-precision control in the program's place
    in the comparison, which must then fail; ``rate`` overrides the mix's
    rate for the knee sweep.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bm = load_benchmark()
    cell, conf = find_cell(bm, workload)
    conf = config or conf
    mix = mix or gen.load_mix(cell["traffic"])

    import jax
    import jax.numpy as jnp
    devices = jax.devices(platform)          # raises where there is none
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} chips, "
                         f"found {len(devices)}")
    dev = devices[0]
    if jax.devices()[0] != dev:
        raise SystemExit(f"default device is {jax.devices()[0]}, not {dev}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device: {json.dumps(device)}")

    from repro.core.policy import OffloadPolicy
    from repro.launch.cache import use_compile_cache
    from repro.models import build_model
    from repro.obs import hwcounters
    from repro.obs import trace as rtrace
    from repro.serve import BatchedServer, ServeConfig

    if platform == "tpu":
        # every program, however quick to compile, comes from the cache
        # after a cell's first run in a checkout
        log(f"compile cache: {use_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"host counter tier: {hwcounters.probe().tier}")
    compiles: list = []

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles.append((time.perf_counter(), kw.get("fun_name", "?"),
                             secs))
    jax.monitoring.register_event_duration_secs_listener(on_event)

    model_mod = importlib.import_module(f"bench.models.{conf['model']}")
    arch = model_mod.Arch.from_dict(conf["arch"])
    dtype = jnp.dtype(conf["dtype"])
    cfg = program_config(conf)
    model = build_model(cfg)
    _check_layout(model, arch, model_mod, dtype)
    params = jax.block_until_ready(
        model_mod.init_weights(model_mod.seed_words(seed), arch, dtype))
    log(f"weights: {model_mod.param_count(arch)} params "
        f"{sum(x.nbytes for x in jax.tree.leaves(params))} bytes "
        f"at {time.perf_counter() - t_start:.3f} s")

    serve = conf["serve"]
    n_new, length = mix["new_tokens"], mix["prompt_len"]
    if length + n_new > serve["max_len"]:
        raise SystemExit(f"{length}+{n_new} tokens exceed max_len "
                         f"{serve['max_len']}")
    run = Run(workload, seed, seconds, cell["chips"], arch, model_mod, mix)
    server = BatchedServer(
        model, params,
        ServeConfig(max_len=serve["max_len"], max_batch=serve["max_batch"],
                    max_new_tokens=n_new),
        OffloadPolicy(max_batch=serve["max_batch"]))
    session = rtrace.enable(capacity=TRACE_RING_RECORDS) if trace else None
    ctx = mp.get_context("spawn")
    ready, results = ctx.Queue(), ctx.Queue()
    go, t0_value = ctx.Event(), ctx.Value("d", 0.0)
    plans = gen.open_schedule(mix, seed, seconds, rate)
    procs, got = [], []
    trace_dir = None
    try:
        with server.serve_over_ipc() as fabric:
            procs = [ctx.Process(target=client_main, daemon=True, args=(
                i, fabric.name, serve["max_batch"], mix, seed,
                arch.vocab_size, plans[i], ready, go, t0_value, seconds,
                results)) for i in range(mix["clients"])]
            for p in procs:
                p.start()

            # warm every program the window can call: each batch size's
            # prefill, decode, and the eager ops between them
            for b in range(1, serve["max_batch"] + 1):
                toks = np.zeros((b, length), np.int32)
                server.generate_batch({"tokens": toks},
                                      new_tokens=min(n_new, 2))
                jnp.concatenate([jnp.zeros((b, 1), jnp.int32)] * n_new,
                                axis=1).block_until_ready()
            warm = len(compiles)
            log(f"warm-up: {warm} compiles, "
                f"{sum(c[2] for c in compiles):.3f} s compiling, done at "
                f"{time.perf_counter() - t_start:.3f} s")
            _wait_ready(ready, procs, mix["clients"])

            run.batches = recorded = []
            orig = server.generate_batch

            def recorder(batch, new_tokens=None):
                toks = np.array(batch["tokens"], copy=True)
                b0 = time.perf_counter_ns()
                out = orig(batch, new_tokens)
                b1 = time.perf_counter_ns()
                if fault == "token":        # planted: altered where made
                    out = (out + 1) % arch.vocab_size
                recorded.append((b0, b1, toks, np.array(out, copy=True)))
                return out
            server.generate_batch = recorder

            run.t0 = time.perf_counter() + 0.05
            run.t_end = run.t0 + seconds
            run.setup_s = run.t0 - t_start
            t0_value.value = run.t0
            go.set()
            sleep_until(run.t0)
            cpu0 = time.process_time()
            run.disp0 = dict(vars(fabric.dispatcher.stats))
            if trace:
                tw = min(mix["trace_seconds"], seconds)
                sleep_until(run.t0 + (seconds - tw) / 2)
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                # no Python tracer: it would slow every thread of this
                # process; the program's spans name the host's time
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                m0 = time.perf_counter_ns()
                with jax.profiler.TraceAnnotation(xtrace.MARKER):
                    pass
                m1 = time.perf_counter_ns()
                sleep_until(run.t0 + (seconds + tw) / 2)
                run.trace_lo, run.trace_hi = m0, time.perf_counter_ns()
                jax.profiler.stop_trace()
            sleep_until(run.t_end)
            run.cpu_s = time.process_time() - cpu0
            run.disp1 = dict(vars(fabric.dispatcher.stats))
            conns = fabric.stats()["clients"]       # who is still served
            in_window = [c for c in compiles[warm:] if c[0] < run.t_end]
            got = _collect(results, procs, mix["clients"])
            dstats = dict(vars(fabric.dispatcher.stats))
            rstats = fabric.stats()["reactor"]
        for p in procs:
            p.join(30)
    finally:
        for p in procs:
            if p.pid is None:
                continue
            if p.is_alive():
                p.terminate()
            p.join(10)
        if session is not None:
            run.spans = rtrace.collect(session, unlink=True)
            rtrace.disable()

    for g in got:
        run.cpu_s += g["cpu_s"]
        for i, k in enumerate(g["k"]):
            run.requests.append({
                "client": g["client"], "k": k, "due": g["due"][i],
                "sent": g["sent"][i], "done": g["done"][i],
                "tokens": g["tokens"][i]})
    jax_clients = [g["client"] for g in got if g["jax_backend"]]
    log(f"clients: retries={[g['retries'] for g in got]} "
        f"dup_replies={[g['dup_replies'] for g in got]} "
        f"lost_replies={[g['lost_replies'] for g in got]} "
        f"reconnects={[g['reconnects'] for g in got]} "
        f"errors={sum(len(g['errors']) for g in got)}; dispatcher "
        f"dedup_hits={dstats['dedup_hits']} shed={dstats['shed']} "
        f"mean_batch={dstats['mean_batch']:.3f}")
    log("reactor: " + " ".join(
        f"{k}={rstats[k]}" for k in ("stale_reaped", "orphan_reaped",
                                     "disconnects", "errors", "throttled")))
    log("connections at the close (cid received/replied/inflight): "
        + " ".join(f"{cid}:{c['received']}/{c['replied']}/{c['inflight']}"
                   for cid, c in conns.items()))
    for g in got:
        for e in g["errors"][:3]:
            log(f"client {g['client']} error: {e}")
    log(f"compiles in the window: {len(in_window)} "
        f"{[c[1] for c in in_window][:5]}")
    _log_backlog(run)
    if run.spans is not None:
        log(f"spans: drops={run.spans.total_drops} per completed request "
            f"(us): " + json.dumps({
                k: round(v[1] / 1e3 / max(run.completed_in_window(), 1), 3)
                for k, v in run.spans.phase_totals().items()}))
    mem = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))

    if trace_dir is not None:
        try:
            run.dtrace = _read_profile(trace_dir, m0, m1)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    server.close()
    # the program's state goes before the reference runs on the chip
    del server, orig, recorder, params, fabric
    gc.collect()
    run.batches = [(b0, b1, t.shape[0], t.shape[1])
                   for b0, b1, t, _ in recorded]

    checks, readings = check_outputs(run, conf, recorded, control)
    checks = {"jax_clients": {"value": len(jax_clients), "limit": 0},
              **checks}
    metrics = {}
    for m in cell_metrics(bm, workload, trace):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
            "attempted": len(run.window_requests()),
            "failed": checks["failed_requests"]["value"],
            "metrics": metrics, "device": device}
    if trace:
        lo, hi = run.trace_lo, run.trace_hi
        device["window_s"] = (hi - lo) / 1e9
        if run.dtrace is not None:
            device["busy_s"] = xtrace.busy_ns(run.dtrace, lo, hi) / 1e9
            line["breakdown"] = breakdown(run)
    line["load"] = load_readings(run)
    line["readings"] = readings
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return line


def load_readings(run: Run) -> dict:
    """The load a run offered and met, in every run beside its metrics:
    a starved generator or a perturbed batch size must show."""
    late = [(r["sent"] - r["due"]) * 1e3 for r in run.window_requests()]
    b = run.disp1["batches"] - run.disp0["batches"]
    r = run.disp1["batched_requests"] - run.disp0["batched_requests"]
    return {"gen_late_p95_ms": gen.percentile(late, 95) if late else None,
            "mean_batch": r / b if b else None,
            "completed_per_s": run.completed_in_window() / run.seconds}


def _log_backlog(run: Run) -> None:
    """Whether a backlog grew through the window: completions per second
    against the offered rate, and latency in each half of the window."""
    reqs = run.window_requests()
    mid = (run.t0 + run.t_end) / 2
    halves = [[(r["done"] - r["due"]) * 1e3 for r in reqs
               if (r["due"] < mid) == first] for first in (True, False)]
    p50 = [gen.percentile([x if x == x else math.inf for x in h], 50)
           if h else None for h in halves]
    log(f"window: {len(reqs)} due, {run.completed_in_window()} completed "
        f"({run.completed_in_window() / run.seconds:.3f} req/s); p50 ms "
        f"first half {p50[0]}, second half {p50[1]}")


def _wait_ready(ready, procs, n: int) -> None:
    seen = 0
    deadline = time.perf_counter() + 600
    while seen < n:
        try:
            ready.get(timeout=1.0)
            seen += 1
        except queue.Empty:
            dead = [p.exitcode for p in procs if p.exitcode is not None]
            if dead or time.perf_counter() > deadline:
                raise SystemExit(f"clients not ready: exit codes {dead}")


def _collect(results, procs, n: int) -> list:
    got = []
    deadline = time.perf_counter() + GRACE_S + 120
    while len(got) < n:
        try:
            got.append(results.get(timeout=1.0))
        except queue.Empty:
            failed = [p.exitcode for p in procs
                      if p.exitcode not in (None, 0)]
            if failed or time.perf_counter() > deadline:
                raise SystemExit(f"clients failed: exit codes {failed}")
    return sorted(got, key=lambda g: g["client"])


def _read_profile(trace_dir: str, m0: int, m1: int):
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        log("trace: no xplane file written")
        return None
    pd = xtrace.load(str(files[-1]))
    dt = xtrace.device_trace(pd, xtrace.clock_offset_ns(pd, m0, m1))
    log("trace planes: " + ", ".join(
        f"{p.name}[{','.join(sorted({l.name for l in p.lines}))}]"
        for p in pd.planes))
    if not dt.ops:
        log("trace: no device plane with an ops line")
        return None
    return dt


def breakdown(run: Run) -> dict:
    lo, hi = run.trace_lo, run.trace_hi
    busy = [(a, b) for evs in run.dtrace.ops.values() for _, a, b in evs]
    _, gaps = xtrace.union(busy, lo, hi)
    spans = []
    if run.spans is not None:
        for name in GAP_SPANS:
            recs = run.span_records(name, lo - 60e9, hi)
            spans += [(name, float(r["t0"]), float(r["t1"])) for r in recs]
    return {"device_ops": xtrace.top_ops(run.dtrace, lo, hi),
            "idle_gaps": xtrace.name_gaps(gaps, spans)}


# ---------------------------------------------------------------------------
# correct: what came back to the clients against the plain reference
# ---------------------------------------------------------------------------

def check_outputs(run: Run, conf: dict, recorded: list,
                  control: bool) -> tuple:
    """Checks ``{name: {"value", "limit"}}`` and the gap readings.  With
    ``control`` the control's first-ranked tokens stand in the served
    tokens' place, at the same prompts and positions."""
    arch, mix, seed = run.arch, run.mix, run.seed
    n_new, length = mix["new_tokens"], mix["prompt_len"]
    window = run.window_requests()
    failed = sum(1 for r in window if not math.isfinite(r["done"]))
    replies, malformed = {}, 0
    for r in run.requests:
        out = r["tokens"]
        if out is None:
            continue
        if (out.shape != (n_new,) or out.dtype.kind not in "iu"
                or out.min() < 0 or out.max() >= arch.vocab_size):
            malformed += 1
            continue
        p = gen.prompt(seed, r["client"], r["k"], length, arch.vocab_size)
        replies[p.tobytes()] = out
    rng = np.random.default_rng([seed, 3])
    gaps = []
    if arch.moe:
        if n_new != 1:
            raise NotImplementedError("an MoE decode comparison needs each "
                                      "step's batch composition")
        # capacity couples a row to its batchmates: compare whole batches
        want = mix["check_requests"]
        rows = 0
        for i in rng.permutation(len(recorded)):
            toks = recorded[i][2]
            b = toks.shape[0]
            # pad rows (no capacity taken) to a few sizes, so that the
            # reference's programs are reused from batch to batch
            pad = min(conf["serve"]["max_batch"], 1 << (b - 1).bit_length())
            full = np.zeros((pad, length), np.int32)
            full[:b] = toks
            served = [replies.get(t.tobytes()) for t in toks]
            _compare(run, full, b, 1, served, gaps, control)
            rows += b
            if rows >= want:
                break
    else:
        done = [r for r in run.requests if r["tokens"] is not None]
        pick = [done[i] for i in rng.permutation(len(done))
                [: mix["check_requests"]]]
        toks = np.stack([np.concatenate([
            gen.prompt(seed, r["client"], r["k"], length, arch.vocab_size),
            r["tokens"][:-1]]) for r in pick])
        served = [r["tokens"] for r in pick]
        _compare(run, toks, len(pick), n_new, served, gaps, control)
    missing = sum(1 for g in gaps if g is None)
    found = [g for g in gaps if g is not None]
    want = mix["check_requests"] * n_new
    readings = _gap_readings(found)
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "malformed_replies": {"value": malformed + missing, "limit": 0},
        "tokens_short": {"value": max(0, want - len(gaps)), "limit": 0},
    }
    # the configuration names the gap statistics it is held to
    for name, limit in conf["correct"].items():
        checks[name] = {"value": readings[name], "limit": limit}
    return checks, readings


def _gap_readings(gaps: list) -> dict:
    """The widest and the mean gap of the compared tokens."""
    if not gaps:
        return {"max_logit_gap": math.inf, "mean_logit_gap": math.inf}
    return {"max_logit_gap": float(max(gaps)),
            "mean_logit_gap": float(np.mean(gaps))}


def _compare(run: Run, toks: np.ndarray, rows: int, last: int, served: list,
             gaps: list, control: bool) -> None:
    """Append, per served token, how far its reference logit lies below
    the reference's best (``None`` where no reply came back); with
    ``control``, the same for the token the control ranks first."""
    ref = run.model.logits(run.arch, run.seed, toks, last, rows=rows)
    if control:
        low = run.model.logits(run.arch, run.seed, toks, last, rows=rows,
                               control=True)
    for i in range(rows):
        if served[i] is None:
            gaps.append(None)
            continue
        pick = low[i].argmax(-1) if control else served[i]
        lg = ref[i]
        gaps.extend((lg.max(-1) - lg[np.arange(last), pick]).tolist())
