"""Serving launcher: run a TaiChi (or baseline) cluster.

Three modes:
  --engine sim   event-driven simulator with estimator timing (default;
                 any registered arch, production scale)
  --engine jax   real JAX engine on the local device, batch replay
  --engine live  the ONLINE serving runtime on the real JAX engine:
                 open-loop ingestion, per-token streaming, windowed
                 telemetry snapshots, and (with --controller) live
                 slider adaptation incl. drain-and-flip role changes
and ``--serve HOST:PORT``, the OpenAI-compatible HTTP/SSE server on the
live runtime.

The real-engine modes build the model at its published widths (bf16)
with the paged Pallas kernels lowered natively on a TPU, and size each
instance's KV pool from the device's memory.  ``--reduced`` builds the
2-layer float32 smoke variant instead, at CPU sizes (8 slots, 512
positions, 512 blocks), with the jnp attention read.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b \
      --policy taichi --np 2 --nd 2 --sp 1024 --sd 256 --qps 80
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --engine jax --reduced --arch smollm-135m --qps 2 --n 16
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
      --engine live --reduced --arch smollm-135m --qps 3 --n 24 \
      --controller --stream
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --serve 0.0.0.0:8000
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
from typing import Optional

import jax

from repro.configs import get_config, reduced_config
from repro.core.cluster import Cluster
from repro.core.latency import SLO
from repro.core.policies import Sliders
from repro.models.config import ModelConfig
from repro.sim.simulator import ServingConfig, build_cluster, run_sim
from repro.sim.workload import WORKLOADS, LengthDist, WorkloadSpec

#: reduced-config live/jax demo traffic (tokenized: the engine sees real
#: token ids, so runs are reproducible across loops)
TINY = WorkloadSpec("tiny",
                    LengthDist(mu=3.4, sigma=0.4, lo=16, hi=128),
                    LengthDist(mu=2.5, sigma=0.4, lo=4, hi=32),
                    tokenized=True, vocab_size=4096)

#: ``--reduced`` engine sizes: the 2-layer smoke model on a CPU
REDUCED_SIZES = dict(n_slots=8, max_ctx=512, hbm_blocks=512)

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def compile_cache_dir() -> str:
    """Where this program keeps JAX's persistent compile cache:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the
    root of the checkout.  The fallback is a fixed path, so a second run
    finds what the first one compiled."""
    return os.environ.get(_CACHE_ENV) or str(_REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on before the first compile.
    JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself; only without it is
    a directory set here."""
    path = compile_cache_dir()
    if not os.environ.get(_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------------
# the real-engine cluster
# ---------------------------------------------------------------------------

def model_config(arch: str, reduced: bool = False) -> ModelConfig:
    """Published widths (bf16) by default; the smoke variant with
    ``reduced``."""
    return reduced_config(arch) if reduced else get_config(arch)


def kv_pool_blocks(cfg: ModelConfig, n_instances: int, block_size: int,
                   device=None, transient_pools: int = 1) -> int:
    """KV blocks per instance that fit the device next to what it
    already holds (the shared weights): ``n_instances`` pools,
    ``transient_pools`` more for the largest pool-sized temporaries one
    program makes (``JaxExecutor.transient_pools``), and the
    step-temporary reserve."""
    from repro.engine.paged import PagedKVCache
    device = device or jax.devices()[0]
    stats = device.memory_stats()
    if not stats or "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device.platform} device reports no memory limit: pass "
            "hbm_blocks (--hbm-blocks) to size the KV pools")
    limit = stats["bytes_limit"]
    # kept back for step temporaries and the runtime
    reserve = max(1 << 30, limit // 8)
    free = limit - stats.get("bytes_in_use", 0) - reserve
    per_block = block_size * PagedKVCache.token_bytes_for(cfg)
    blocks = free // (n_instances + transient_pools) // per_block
    if blocks < 1:
        raise RuntimeError(
            f"no room for a KV pool: {free} free bytes on {device}")
    return int(blocks)


@dataclasses.dataclass
class Engine:
    """A real-engine cluster and what it was built from."""
    cluster: Cluster
    cfg: ModelConfig
    params: dict
    sc: ServingConfig
    kernels: bool        # paged Pallas kernels on (native lowering)
    pool_bytes: int      # KV pool bytes, all instances


def build_engine(arch: str, slo: SLO, *, reduced: bool = False,
                 policy: str = "taichi", sliders: Optional[Sliders] = None,
                 n_slots: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 hbm_blocks: Optional[int] = None,
                 async_exec: bool = True, horizon: int = 1) -> Engine:
    """Build the JaxExecutor cluster every real-engine mode serves from.

    Sizes not given come from ``ServingConfig`` (``REDUCED_SIZES`` with
    ``reduced``); without ``hbm_blocks`` a published-width pool is sized
    from the device's memory.  The paged kernels are on wherever they
    lower natively (``kernels_native_default``)."""
    from repro.engine.engine import JaxExecutor
    from repro.kernels import kernels_native_default
    from repro.models import attention
    from repro.models import transformer as tf
    cfg = model_config(arch, reduced)
    kernels = kernels_native_default()
    attention.use_kernels(kernels)
    sliders = sliders or Sliders(n_p=2, n_d=2, s_p=1024, s_d=256)
    if reduced:
        sliders = dataclasses.replace(sliders, s_p=min(sliders.s_p, 64),
                                      s_d=min(sliders.s_d, 32))
    sc = ServingConfig(model=arch, tp=1, policy=policy, sliders=sliders,
                       **(REDUCED_SIZES if reduced else {}))
    given = dict(n_slots=n_slots, max_ctx=max_seq, hbm_blocks=hbm_blocks)
    sc = dataclasses.replace(
        sc, **{k: v for k, v in given.items() if v is not None})
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    if hbm_blocks is None and not reduced:
        sc.hbm_blocks = kv_pool_blocks(
            cfg, sliders.n_p + sliders.n_d, sc.block_size,
            transient_pools=JaxExecutor.transient_pools(horizon))
    factory = lambda: JaxExecutor(cfg, params, n_slots=sc.n_slots,
                                  max_seq=sc.max_ctx,
                                  hbm_blocks=sc.hbm_blocks,
                                  cache_block_size=sc.block_size)
    cluster = build_cluster(sc, slo, executor_factory=factory,
                            async_exec=async_exec)
    if horizon > 1:
        cluster.set_horizon(horizon)
    pool_bytes = sum(i.executor.cache_bytes() for i in cluster.instances)
    return Engine(cluster, cfg, params, sc, kernels, pool_bytes)


def exit_on_exec_errors(cluster: Cluster):
    """With no FaultInjector attached, an executor step that raised is a
    real device or program error: the cluster quarantined around it, but
    the run must not end with exit code 0."""
    if cluster.faults is None and cluster.exec_errors:
        print(cluster.last_exec_error, file=sys.stderr, flush=True)
        raise SystemExit(f"{cluster.exec_errors} executor step(s) failed")


def make_server(engine: Engine, slo: SLO, *, host: str = "127.0.0.1",
                port: int = 8000, tok_workers: int = 2,
                adm_depth: int = 256, adm_inflight: int = 64,
                controller=None, window: float = 5.0, tracing=None):
    """The OpenAI-compatible HTTP/SSE server on a wall-clock live loop
    over ``engine``; ``run()`` it, and read ``server.loop`` after."""
    from repro.frontend import AdmissionConfig, FrontendConfig, \
        FrontendServer
    from repro.serving import ServingLoop, WallClock
    loop = ServingLoop(
        engine.cluster, slo, clock=WallClock(), pace=True,
        controller=controller, window=window,
        admission=AdmissionConfig(max_depth=adm_depth,
                                  max_inflight=adm_inflight),
        tracing=tracing)
    return FrontendServer(loop, FrontendConfig(
        host=host, port=port, model=engine.sc.model,
        tok_workers=tok_workers))


# ---------------------------------------------------------------------------
# modes
# ---------------------------------------------------------------------------

def _trace_config(args):
    """--trace (or either output path) turns on lifecycle tracing."""
    if not (args.trace or args.trace_out or args.trace_jsonl):
        return None
    from repro.serving import TraceConfig
    return TraceConfig()


def _dump_trace(loop, args, slo: SLO):
    tr = getattr(loop, "tracer", None)
    if tr is None:
        return
    if args.trace_out:
        tr.dump_chrome(args.trace_out)
        print(f"chrome trace -> {args.trace_out} "
              "(open in ui.perfetto.dev)", flush=True)
    if args.trace_jsonl:
        tr.dump_jsonl(args.trace_jsonl)
        print(f"trace jsonl -> {args.trace_jsonl}", flush=True)
    print(json.dumps(
        {"slo_violation_report": tr.violation_report(slo),
         "wait_report": tr.wait_report()},
        indent=2, default=str))


def _engine(args, slo: SLO, **kw) -> Engine:
    eng = build_engine(
        args.arch, slo, reduced=args.reduced, policy=args.policy,
        sliders=Sliders(n_p=args.np, n_d=args.nd, s_p=args.sp,
                        s_d=args.sd),
        n_slots=args.n_slots, max_seq=args.max_seq,
        hbm_blocks=args.hbm_blocks, **kw)
    print(json.dumps({"engine": eng.cfg.name, "kernels": eng.kernels,
                      "n_slots": eng.sc.n_slots, "max_seq": eng.sc.max_ctx,
                      "hbm_blocks": eng.sc.hbm_blocks,
                      "kv_pool_bytes": eng.pool_bytes}), flush=True)
    return eng


def _controller(args):
    if not args.controller:
        return None
    from repro.serving import ControllerConfig, SliderController
    ladder = {"sd_steps": (16, 32, 64)} if args.reduced else {}
    return SliderController(ControllerConfig(epoch=args.epoch, cooldown=1,
                                             **ladder))


def _live_mode(args, slo: SLO):
    """Online runtime on the real engine: tokens stream as they are
    computed, telemetry snapshots print as JSON lines, and the
    controller may retune sliders mid-run."""
    from repro.serving import ServingLoop, WallClock
    eng = _engine(args, slo, async_exec=not args.no_async,
                  horizon=args.horizon)
    cluster = eng.cluster
    ctl = _controller(args)
    streamed = {"tokens": 0}

    def on_token(req, t, tok):
        streamed["tokens"] += 1
        if args.stream:
            print(f"[{t:8.3f}s] req{req.rid} token#{req.output_len} "
                  f"id={tok}")

    loop = ServingLoop(
        cluster, slo,
        arrivals=TINY.iter_requests(args.qps, seed=0,
                                    max_new_tokens=32, limit=args.n),
        controller=ctl, window=args.window, on_token=on_token,
        snapshot_every=args.snapshot_every,
        clock=WallClock() if args.pace else None, pace=args.pace,
        tracing=_trace_config(args))
    loop.run()
    _dump_trace(loop, args, slo)
    for snap in loop.log.snapshots:
        print(json.dumps({k: v for k, v in snap.items()
                          if k != "instances"}))
    st = loop.stats(args.qps)
    print(json.dumps({**st.summary(),
                      "policy": args.policy,
                      "streamed_tokens": streamed["tokens"],
                      "real_tokens": sum(len(r.output_tokens)
                                         for r in loop.requests),
                      "transfers": cluster.transfer_count,
                      "controller_moves": (ctl.moves if ctl else [])},
                     indent=2, default=str))
    exit_on_exec_errors(cluster)


def _serve_mode(args, slo: SLO):
    """Deployable network front-end: the live JAX engine behind the
    OpenAI-compatible HTTP/SSE server (``repro.frontend``), with the
    multi-process tokenize/detokenize pipeline and the router-side
    admission queue.  Blocks until SIGINT/SIGTERM, then drains."""
    host, _, port = args.serve.rpartition(":")
    eng = _engine(args, slo, async_exec=not args.no_async,
                  horizon=args.horizon)
    srv = make_server(eng, slo, host=host or "127.0.0.1", port=int(port),
                      tok_workers=args.tok_workers,
                      adm_depth=args.adm_depth,
                      adm_inflight=args.adm_inflight,
                      controller=_controller(args), window=args.window,
                      tracing=_trace_config(args))
    print(f"serving {args.arch} ({args.policy}) on "
          f"http://{host or '127.0.0.1'}:{port} — POST /v1/completions, "
          "/v1/chat/completions; GET /healthz, /metrics", flush=True)
    srv.run(install_signals=True)
    print(json.dumps(srv.loop.snapshot(), default=str))
    _dump_trace(srv.loop, args, slo)
    exit_on_exec_errors(eng.cluster)


def _batch_mode(args, slo: SLO):
    """Batch replay of sampled requests on the real engine."""
    cluster = _engine(args, slo, async_exec=False).cluster
    wl = WorkloadSpec("tiny",
                      LengthDist(mu=3.4, sigma=0.4, lo=16, hi=128),
                      LengthDist(mu=2.5, sigma=0.4, lo=4, hi=32))
    reqs = wl.sample_requests(args.n, args.qps, seed=0)
    cluster.run(reqs)
    st = cluster.stats(reqs, slo, args.qps)
    print(json.dumps({**st.summary(),
                      "policy": args.policy,
                      "real_tokens": sum(len(r.output_tokens)
                                         for r in reqs),
                      "transfers": cluster.transfer_count}, indent=2))
    exit_on_exec_errors(cluster)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--engine", choices=["sim", "jax", "live"],
                    default="sim")
    ap.add_argument("--policy", default="taichi",
                    choices=["taichi", "aggregation", "disaggregation"])
    ap.add_argument("--np", type=int, default=2)
    ap.add_argument("--nd", type=int, default=2)
    ap.add_argument("--sp", type=int, default=1024)
    ap.add_argument("--sd", type=int, default=256)
    ap.add_argument("--tp", type=int, default=4)
    ap.add_argument("--qps", type=float, default=40.0)
    ap.add_argument("--n", type=int, default=300)
    ap.add_argument("--workload", default="sharegpt",
                    choices=sorted(WORKLOADS))
    ap.add_argument("--ttft-slo", type=float, default=1.5)
    ap.add_argument("--tpot-slo", type=float, default=0.030)
    # real-engine sizes (jax / live / serve)
    ap.add_argument("--reduced", action="store_true",
                    help="real engine: the 2-layer float32 smoke model at "
                         "CPU sizes instead of the published widths")
    ap.add_argument("--n-slots", type=int, default=None,
                    help="real engine: request rows per instance")
    ap.add_argument("--max-seq", type=int, default=None,
                    help="real engine: longest context per request")
    ap.add_argument("--hbm-blocks", type=int, default=None,
                    help="real engine: KV blocks per instance (default: "
                         "sized from the device's memory)")
    # live-mode knobs
    ap.add_argument("--controller", action="store_true",
                    help="live: adapt sliders online (epoch-based)")
    ap.add_argument("--epoch", type=float, default=2.0,
                    help="live: controller epoch seconds")
    ap.add_argument("--window", type=float, default=5.0,
                    help="live: telemetry window seconds")
    ap.add_argument("--snapshot-every", type=float, default=5.0,
                    help="live: telemetry snapshot cadence")
    ap.add_argument("--stream", action="store_true",
                    help="live: print every streamed token")
    ap.add_argument("--pace", action="store_true",
                    help="live: pace events to wall-clock time")
    ap.add_argument("--horizon", type=int, default=8,
                    help="live: max fused decode steps per iteration "
                         "(adaptive; 1 = classic single-step)")
    ap.add_argument("--no-async", action="store_true",
                    help="live: disable the non-blocking dispatch/"
                         "commit executor pipeline")
    # tracing knobs (live + serve modes)
    ap.add_argument("--trace", action="store_true",
                    help="record per-request lifecycle traces and print "
                         "an SLO violation attribution report")
    ap.add_argument("--trace-out", metavar="PATH", default=None,
                    help="write a Chrome/Perfetto trace JSON after the "
                         "run (implies --trace)")
    ap.add_argument("--trace-jsonl", metavar="PATH", default=None,
                    help="write the trace event log as JSON lines "
                         "(implies --trace)")
    # network front-end knobs
    ap.add_argument("--serve", metavar="HOST:PORT", default=None,
                    help="run the OpenAI-compatible HTTP/SSE server on "
                         "the live engine (e.g. --serve 0.0.0.0:8000)")
    ap.add_argument("--tok-workers", type=int, default=2,
                    help="serve: tokenizer/detokenizer worker processes "
                         "(0 = inline, single-process)")
    ap.add_argument("--adm-depth", type=int, default=256,
                    help="serve: admission queue depth bound")
    ap.add_argument("--adm-inflight", type=int, default=64,
                    help="serve: released-but-unfinished request cap")
    args = ap.parse_args()

    slo = SLO(ttft=args.ttft_slo, tpot=args.tpot_slo)

    if args.serve or args.engine != "sim":
        enable_compile_cache()
    if args.serve:
        return _serve_mode(args, slo)
    if args.engine == "live":
        return _live_mode(args, slo)
    if args.engine == "jax":
        return _batch_mode(args, slo)
    sc = ServingConfig(model=args.arch, tp=args.tp, policy=args.policy,
                       sliders=Sliders(n_p=args.np, n_d=args.nd,
                                       s_p=args.sp, s_d=args.sd))
    st = run_sim(sc, slo, WORKLOADS[args.workload], args.qps, args.n)
    c = st.cluster
    print(json.dumps({**st.summary(),
                      "policy": args.policy,
                      "transfers": c.transfer_count,
                      "backflows": c.backflow_count,
                      "degrades": c.degrade_count}, indent=2))


if __name__ == "__main__":
    main()
