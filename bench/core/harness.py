"""One run of one cell: build the served path, warm it, drive it from a
client process, time the window at the client, check what it served
against the plain reference, and reduce the trace.

The system under test is what ``python -m repro.launch.serve --serve``
runs: ``build_engine`` + ``make_server`` with the configuration's
serving sizes.  From the program the benchmark takes only that server,
the plans its executors are handed (by wrapping ``step_async`` on the
objects it built), the device trace and its kernel and program names.
Every time that decides an end-to-end metric is read from the client's
wall clock.

Everything here touches JAX and runs only in the process that holds the
chip.  The load generator is a separate process (``client.py``) that
never imports JAX.
"""
from __future__ import annotations

import gc
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

from . import trace as T, traffic as traffic_lib, weights
from .manifest import BENCH, Cell

#: compiled programs are kept here, at a fixed path inside the checkout
CACHE_DIR = BENCH / ".jax_cache"
#: the traced stretch of a --trace 1 run: where it starts in the window
#: (share of the window) and how long it lasts (seconds, at most)
TRACE_AT, TRACE_S = 0.3, 4.0
#: served tokens the correctness sample reaches for (besides the longest)
SAMPLE_TOKENS = 2048
SAMPLE_MAX = 16


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class RunFailed(RuntimeError):
    """The run could not produce a result."""


def log(**kw):
    print(json.dumps(kw, default=str), file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# device, cache, model
# ---------------------------------------------------------------------------

def check_device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"need {chips} TPU chip(s); JAX has {len(devs)} "
                     f"{dev.platform} device(s)")
    return dev, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": chips}


def use_compile_cache() -> dict:
    """Keep every compiled program in the checkout's cache directory,
    whatever ``$JAX_COMPILATION_CACHE_DIR`` says, with no minimum
    compile time or size."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"dir": str(CACHE_DIR),
            "env_not_used": os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def build_engine(arch, conf: dict, slo, seed: int):
    """``build_engine`` of the program for ``arch``'s ``ModelConfig``,
    serving the benchmark's seeded weights: the program's own initializer
    is bypassed while it builds, and the tree handed over must match
    what that initializer makes."""
    import jax
    from repro.configs.registry import register
    from repro.core.policies import Sliders
    from repro.launch import serve
    from repro.models import transformer as tf
    cfg = register(arch.model_config(conf))
    params = weights.all_params(arch, conf, seed)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), tf.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise RunFailed("the benchmark's weights do not match the "
                        "program's parameter tree")
    sv = conf["serving"]
    sizes = {k: sv[k] for k in ("n_slots", "max_seq", "hbm_blocks")
             if k in sv}
    orig = tf.init_params
    tf.init_params = lambda key, cfg: params
    try:
        engine = serve.build_engine(
            cfg.name, slo, policy=sv["policy"],
            sliders=Sliders(n_p=sv["n_p"], n_d=sv["n_d"], s_p=sv["s_p"],
                            s_d=sv["s_d"]),
            horizon=sv["horizon"], **sizes)
    finally:
        tf.init_params = orig
    return engine


# ---------------------------------------------------------------------------
# what the benchmark sees of the program while it runs
# ---------------------------------------------------------------------------

class Probe:
    """Plans dispatched and host spans, recorded by wrapping
    objects the benchmark built.  With ``annotate`` each wrapper also
    writes a ``TraceAnnotation`` (``exec.dispatch``, ``exec.readback``,
    ``loop.sleep``) into the profiler's trace."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.dispatches = []       # SimpleNamespace per step_async call
        self.lock = threading.Lock()

    def span(self, name: str, **kw):
        if not self.annotate:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)

    @staticmethod
    def describe(plan) -> SimpleNamespace:
        """Rows of a plan: prefill chunks as (start, take), decode rows
        as (position of the token fed, 1), each horizon step apart."""
        pre = [(r.prefill_pos + r.recompute_offset, take)
               for r, take in plan.prefill_items]
        K = plan.horizon
        budgets = plan.decode_budgets or [1] * len(plan.decode_reqs)
        dec = [(r.context_len - 1, b)
               for r, b in zip(plan.decode_reqs, budgets)]
        if K > 1:
            # one kernel call per fused step; a row runs its budget
            calls = [[(p + s, 1) for p, b in dec if s < b]
                     for s in range(K)]
        else:
            calls = [list(pre) + [(p, 1) for p, _ in dec]]
        return SimpleNamespace(
            kind="horizon" if K > 1 else "mixed", K=K, prefill=pre,
            decode_rows=len(dec), calls=calls,
            rows=[row for call in calls for row in call],
            kernel="paged_prefill" if pre else "paged_decode")

    def instrument(self, engine):
        for inst in engine.cluster.instances:
            ex = inst.executor
            ex.step_async = self._wrap_step(ex.step_async, inst.iid)
            if self.annotate:
                inst.build_plan = self._spanned(inst.build_plan, T.PLAN)
                inst.commit_iteration = self._spanned(
                    inst.commit_iteration, T.COMMIT)

    def _spanned(self, fn, name):
        def call(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return call

    def _wrap_step(self, orig, iid):
        def step_async(plan):
            info = self.describe(plan)
            with self.lock:
                info.seq = len(self.dispatches)
                self.dispatches.append(info)
            info.t0 = time.monotonic()
            with self.span(T.DISPATCH, seq=info.seq, iid=iid,
                           kind=info.kind):
                pending = orig(plan)
            info.t1 = time.monotonic()
            if self.annotate and hasattr(pending, "prefetch"):
                fetch = pending.prefetch

                def prefetch():
                    with self.span(T.READBACK, seq=info.seq):
                        return fetch()
                pending.prefetch = prefetch
            return pending
        return step_async

    def clock(self):
        """A ``WallClock`` whose sleeps are annotated in a trace."""
        from repro.serving.clock import WallClock
        probe = self

        class BenchClock(WallClock):
            def sleep_until(self, t):
                with probe.span(T.SLEEP):
                    WallClock.sleep_until(self, t)

        return BenchClock()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def jit_compiles(engine) -> int:
    return sum(i.executor.jit_compiles() for i in engine.cluster.instances)


# ---------------------------------------------------------------------------
# the byte tokenizer's rendering, for checking what the client received
# ---------------------------------------------------------------------------

def render(ids) -> str:
    """Text the byte tokenizer streams for ``ids``: bytes below 256 as
    UTF-8 (broken sequences replaced), other ids as ``⟨id⟩``."""
    import codecs
    dec = codecs.getincrementaldecoder("utf-8")(errors="replace")
    out = []
    for i in ids:
        if 0 <= i < 256:
            out.append(dec.decode(bytes([i])))
        else:
            out.append(dec.decode(b"", True) + f"⟨{i}⟩")
    out.append(dec.decode(b"", True))
    return "".join(out)


# ---------------------------------------------------------------------------
# end-to-end metrics from the client's records
# ---------------------------------------------------------------------------

def in_window(rec: dict, window) -> bool:
    return rec["due"] is not None and window[0] <= rec["due"] <= window[1]


def ok(rec: dict) -> bool:
    """Answered in full, without an error."""
    return rec["status"] == 200 and rec["error"] is None \
        and rec["finish"] is not None


def failed(rec: dict) -> bool:
    """Refused, broken off or answered with an error; a request that was
    only late (still streaming at the drain cap) has not failed."""
    return not ok(rec) and not rec["late"]


def end_to_end(client: dict, mix: dict, seconds: float) -> dict:
    """Client-side numbers over the requests due in the window.

    A request that failed, was refused, or had not received its first
    token when the drain cap ran out counts with the time it had waited
    by then (its TTFT is at least that) and misses both limits.  A
    request still streaming at the cap (late) counts with the tokens it
    had, and misses the limits."""
    window = client["window"]
    recs = [r for r in client["records"] if in_window(r, window)]
    cap_end = window[1] + client["drain_s"]
    ttft, tpot, met = [], [], 0
    for r in recs:
        if r["first"] is not None:
            ttft.append(r["first"] - r["due"])
        else:
            ended = r["ended"] if r["ended"] is not None else cap_end
            ttft.append(ended - r["due"])
        if r["first"] is not None and r["tokens"] > 1:
            tpot.append((r["last"] - r["first"]) / (r["tokens"] - 1))
        slo = mix["slo"]
        if ok(r) and r["first"] is not None \
                and r["first"] - r["due"] <= slo["ttft_s"] \
                and (r["tokens"] <= 1 or (r["last"] - r["first"])
                     / (r["tokens"] - 1) <= slo["tpot_s"]):
            met += 1
    out = {"attempted": len(recs),
           "failed": sum(failed(r) for r in recs),
           "late": sum(r["late"] for r in recs),
           "output_tok_s": client["tokens_in_window"] / seconds}
    if ttft:
        out["ttft_p90_ms"] = traffic_lib.percentile(ttft, 90) * 1e3
        out["ttft_p50_ms"] = traffic_lib.percentile(ttft, 50) * 1e3
    if tpot:
        out["tpot_p90_ms"] = traffic_lib.percentile(tpot, 90) * 1e3
        out["tpot_p50_ms"] = traffic_lib.percentile(tpot, 50) * 1e3
    if recs:
        out["slo_attainment"] = 100.0 * met / len(recs)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def start_client(spec: dict):
    proc = subprocess.Popen(
        [sys.executable, str(BENCH / "core" / "client.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    proc.stdin.write(json.dumps(spec))
    proc.stdin.close()
    proc.stdin = None            # so that communicate() leaves it alone
    line = proc.stdout.readline()
    if not line:
        proc.wait(timeout=30)
        raise RunFailed(f"load generator exited ({proc.returncode})")
    return proc, json.loads(line)["start"]


def sleep_to(t: float):
    dt = t - time.monotonic()
    if dt > 0:
        time.sleep(dt)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        fault: Optional[Callable] = None, compile_cache: bool = True,
        control: bool = False) -> dict:
    """One run; returns the result line's object.  ``fault`` (tests and
    ``bench/control.py`` only) is applied to the built engine to break
    the timed path.  With ``control`` (``bench/control.py`` only) the
    int8 control is judged in the program's place, and the program's own
    gap numbers on the same sample come under ``program_gap_numbers``."""
    import jax
    chips = int(cell.entry["chips"])
    dev, device = check_device(chips, require_tpu)
    cache = use_compile_cache() if compile_cache else None
    peaks = cell.peaks(dev.device_kind) if require_tpu else \
        next(iter(cell.peaks_table.values()))
    log(device=device, compile_cache=cache, cell=cell.name, seed=seed)
    from repro.core.latency import SLO
    from repro.launch import serve
    mix, conf = cell.traffic, cell.conf
    slo = SLO(ttft=mix["slo"]["ttft_s"], tpot=mix["slo"]["tpot_s"])
    compile_events = {"compiles": 0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_events["compiles"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compile_events["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    t_build = time.monotonic()
    engine = build_engine(cell.arch, conf, slo, seed)
    stats = dev.memory_stats() or {}
    log(built_s=time.monotonic() - t_build, kernels=engine.kernels,
        hbm_blocks=engine.sc.hbm_blocks, kv_pool_bytes=engine.pool_bytes,
        bytes_in_use=stats.get("bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))
    if fault is not None:
        fault(engine)
    probe = Probe(annotate=trace)
    probe.instrument(engine)
    sv = conf["serving"]
    srv = serve.make_server(engine, slo, host="127.0.0.1", port=0,
                            tok_workers=sv["tok_workers"],
                            adm_depth=sv["adm_depth"],
                            adm_inflight=sv["adm_inflight"])
    srv.loop.clock = probe.clock()
    cap = srv.cfg.max_tokens_cap
    th = threading.Thread(target=srv.run, name="frontend", daemon=True)
    th.start()
    proc = None
    trace_dir = None
    try:
        if not srv.started.wait(timeout=300):
            raise RunFailed("server did not start")
        proc, t0 = start_client({"host": "127.0.0.1", "port": srv.port,
                                 "traffic": mix, "seed": seed,
                                 "seconds": seconds})
        w0, w1 = t0 + mix["ramp_s"], t0 + mix["ramp_s"] + seconds
        sleep_to(w0)
        setup_s = time.monotonic() - t_start
        c0 = (jit_compiles(engine), dict(compile_events))
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            sleep_to(w0 + TRACE_AT * seconds)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(T.WINDOW):
                time.sleep(min(TRACE_S, (1 - TRACE_AT) * seconds / 2))
            jax.profiler.stop_trace()
        sleep_to(w1)
        c1 = (jit_compiles(engine), dict(compile_events))
        out, _ = proc.communicate(
            timeout=mix["drain_cap_s"] + 120)
        if proc.returncode != 0:
            raise RunFailed(f"load generator exited {proc.returncode}")
        client = json.loads(out.strip().splitlines()[-1])
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        # the client has closed its connections: let the aborts land and
        # the in-flight work leave before the server drains and stops
        settle = time.monotonic() + 60
        while srv.loop._inflight and time.monotonic() < settle \
                and srv._engine_thread is not None \
                and srv._engine_thread.is_alive():
            time.sleep(0.1)
        engine_alive = (srv._engine_thread is not None
                        and srv._engine_thread.is_alive())
        srv.shutdown()
        th.join(timeout=120)
    if th.is_alive():
        log(server_not_stopped=True)
    log(window_jit_traces=c1[0] - c0[0],
        window_backend_compiles=c1[1]["compiles"] - c0[1]["compiles"],
        window_cache_hits=c1[1]["cache_hits"] - c0[1]["cache_hits"],
        generator_late_max_s=client["lateness_max_s"],
        generator_late_mean_s=client["lateness_mean_s"],
        drain_s=client["drain_s"], transfers=engine.cluster.transfer_count,
        exec_errors=engine.cluster.exec_errors,
        last_exec_error=(str(engine.cluster.last_exec_error)[-2000:]
                         if engine.cluster.exec_errors else None))
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    served = {r.rid: SimpleNamespace(
        prompt=list(r.prompt_tokens or []), out=list(r.output_tokens),
        state=r.state.value, finish=r.finish_reason,
        max_new=r.max_new_tokens, migrated=r.n_migrations > 0)
        for r in srv.loop.requests}
    exec_errors = engine.cluster.exec_errors
    trace_view = None
    if trace:
        trace_view = T.TraceView(T.TraceView.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    # free the program's state before the reference runs on the chip
    del srv, engine, th
    gc.collect()
    jax.clear_caches()
    e2e = end_to_end(client, mix, seconds)
    compared, program_gaps = check(cell, seed, client, served, cap,
                                   exec_errors, control)
    compared = {"engine_died": {"value": int(not engine_alive), "limit": 0},
                **compared}
    result = {"correct": all(v["value"] <= v["limit"]
                             for v in compared.values()),
              "attempted": e2e["attempted"], "failed": e2e["failed"]}
    if trace:
        ctx = SimpleNamespace(trace=trace_view, probe=probe, conf=conf,
                              arch=cell.arch, peaks=peaks, window=(w0, w1))
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = trace_view.busy_s()
        device["window_s"] = trace_view.window_s()
        result["breakdown"] = {
            "device_ops": T.top_ops(trace_view.ops_in_window()),
            "idle_gaps": trace_view.idle_gaps()}
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in cell.end_to_end:
            if m["name"] != "setup_s" and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    log(client_side={k: v for k, v in e2e.items()})
    if control:
        result["program_gap_numbers"] = program_gaps
    result.update(metrics=metrics, device=device, compared=compared)
    return result


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def check(cell: Cell, seed: int, client: dict, served: dict, cap: int,
          exec_errors: int, control: bool = False):
    """What the run served (ramp, window and drain), against what was
    asked and against the plain reference.  Returns ``{name: {"value",
    "limit"}}``, with the gap numbers that the configuration's
    ``correct`` names, and, with ``control``, the program's own gap
    numbers on the sample (else None): the gap numbers compared are then
    the int8 control's, put in the program's place."""
    from . import reference
    # every request of the run, the ramp's too: what the served path
    # produced while the window ran includes answers to earlier sends
    recs = client["records"]
    errors = wrong_text = wrong_count = 0
    done = []
    for r in recs:
        rid = None
        if r["server_id"] and r["server_id"].startswith("cmpl-"):
            rid = int(r["server_id"][len("cmpl-"):])
        s = served.get(rid)
        if failed(r) or (s is None and not r["late"]):
            errors += 1
            continue
        if s is None:
            continue                # late, and no token streamed yet
        text = render(s.out)
        if (not text.startswith(r["text"])) if r["late"] \
                else text != r["text"]:
            wrong_text += 1
            log(text_mismatch={"rid": rid, "client": r["text"][:200],
                               "served": text[:200]})
        if r["late"]:
            continue
        if s.state != "finished" or len(s.out) != min(s.max_new, cap) \
                or r["finish"] != "length":
            wrong_count += 1
        done.append(s)
    # nothing finished: nothing to vouch for
    nums = {k: 1e30 for k in reference.GAP_NUMBERS}
    program = None
    if done:
        rng = random.Random(seed)
        # the longest, and the longest whose KV moved between instances
        sample = [max(done, key=lambda s: len(s.out))]
        moved = [s for s in done if s.migrated and s is not sample[0]]
        if moved:
            sample.append(max(moved, key=lambda s: len(s.out)))
        rest = [s for s in done if all(s is not t for t in sample)]
        rng.shuffle(rest)
        n = sum(len(s.out) for s in sample)
        for s in rest:
            if n >= SAMPLE_TOKENS or len(sample) >= SAMPLE_MAX:
                break
            sample.append(s)
            n += len(s.out)
        t = time.monotonic()
        seqs = [(s.prompt, s.out) for s in sample]
        logits = reference.served_logits(cell.arch, cell.conf, seed, seqs)
        nums = reference.gap_numbers(
            [reference.gaps(lg, s.out) for lg, s in zip(logits, sample)])
        if control:
            # the control in the program's place: at each position the
            # token the int8 network puts first, judged as served
            program = nums
            ctrl = reference.served_logits(cell.arch, cell.conf, seed, seqs,
                                           precision="int8")
            nums = reference.gap_numbers(
                [reference.gaps(lg, c.argmax(-1))
                 for lg, c in zip(logits, ctrl)])
        log(reference_s=time.monotonic() - t, reference_sequences=len(sample),
            reference_tokens=sum(len(s.out) for s in sample),
            reference_migrated=sum(s.migrated for s in sample),
            gap_numbers=nums, program_gap_numbers=program)
    compared = {
        "executor_errors": {"value": exec_errors, "limit": 0},
        "failed_requests": {"value": errors, "limit": 0},
        "text_not_served": {"value": wrong_text, "limit": 0},
        "token_count_wrong": {"value": wrong_count, "limit": 0},
    }
    for name, limit in cell.conf["correct"].items():
        compared[name] = {"value": nums[name], "limit": limit}
    return compared, program
