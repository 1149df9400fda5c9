"""Chip smoke test: serve smollm-135m at its published widths on one TPU
through the HTTP server, and check what comes back.

    python chip_smoke.py

Builds the real-engine cluster the way ``python -m repro.launch.serve
--serve`` does with its defaults (``build_engine`` + ``make_server``):
30 layers, bf16, random weights from a seed, the TaiChi policy with 2
P-heavy and 2 D-heavy instances (chunks of 1,024 and 256 tokens, an
8-step decode horizon) on the one chip, paged Pallas kernels lowered
natively.  Then it

  * sends 8 concurrent ``/v1/completions`` requests (half of them
    streaming, prompts of 64 to 1,024 tokens, 32 tokens each) and checks
    every answer, that no executor step failed, and that at least one
    request's KV migrated from a P-heavy to a D-heavy instance;
  * checks that the compiled served steps (the mixed prefill+decode
    step and the fused decode horizon), at the largest shapes the
    server can ask for, hold the Pallas kernels (``tpu_custom_call``)
    and fit the device next to the KV pools;
  * compares the logits of one 300-token prompt (two prefill chunks,
    then 8 decode steps) through the paged kernels and through the jnp
    gather read, with the same weights in bf16.

Earlier lines print facts of the run; the last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check
passed.  Without a TPU the script exits non-zero and prints no result.
Everything touching JAX runs under ``__main__``: the tokenizer workers
are spawned processes that re-import this file and must never open the
chip.
"""
import http.client
import json
import os
import pathlib
import random
import sys
import threading
import time

ARCH = "smollm-135m"
N_REQUESTS = 8
MAX_TOKENS = 32
PROMPT_LENS = [64 + i * (1024 - 64) // (N_REQUESTS - 1)
               for i in range(N_REQUESTS)]
CLIENT_TIMEOUT_S = 900
#: kernel vs jnp logits, as max |difference| over max |jnp logit|.  Both
#: paths read the same bf16 K/V from the pool; they differ in how the
#: attention is accumulated (the kernels in float32, the jnp read with
#: bf16 operands for the scores and the weighted sum).  One bf16
#: rounding is 2^-8 of a value, and the gap grows with depth like a
#: random walk: on a CPU, with the kernels interpreted, it measured
#: 0.006 at 2 layers, 0.012 at 6 and 0.016 at 12 of these widths, so
#: about 0.026 is expected at 30.  2^-4 (16 roundings of the largest
#: logit) leaves room for that; a prefill kernel that lets a query see
#: one future key measured 0.61 at 2 layers.
LOGIT_TOL = 2.0 ** -4
COMPARE_CHUNK = 150          # two prefill chunks: a 300-token prompt
COMPARE_DECODE = 8
HORIZON = 8                  # fused decode steps, as ``--serve`` runs


class CheckFailed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise CheckFailed(what)


def fact(**kw):
    print(json.dumps(kw, default=str), flush=True)


# ---------------------------------------------------------------------------
# client side
# ---------------------------------------------------------------------------

def make_prompts():
    """ASCII prompts (one byte = one token) of PROMPT_LENS tokens."""
    rng = random.Random(0)
    words = ["taichi", "prefill", "decode", "slider", "chunk", "tensor",
             "latency", "goodput", "block", "cache", "request", "token"]
    out = []
    for n in PROMPT_LENS:
        text = ""
        while len(text) < n:
            text += rng.choice(words) + " "
        out.append(text[:n])
    return out


def post_completion(port: int, prompt: str, stream: bool):
    """One /v1/completions call: (status, finish_reason, completion
    tokens or None for a stream, error text)."""
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=CLIENT_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(
            {"prompt": prompt, "max_tokens": MAX_TOKENS,
             "stream": stream}), headers={"Content-Type":
                                          "application/json"})
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    if resp.status != 200:
        return resp.status, None, None, body.decode(errors="replace")
    if not stream:
        obj = json.loads(body)
        return (200, obj["choices"][0]["finish_reason"],
                obj["usage"]["completion_tokens"], "")
    finish, done, err = None, False, ""
    for ev in body.split(b"\n\n"):
        if not ev.startswith(b"data: "):
            continue
        if ev == b"data: [DONE]":
            done = True
            continue
        obj = json.loads(ev[len(b"data: "):])
        if "choices" not in obj:
            err = json.dumps(obj)
            continue
        finish = obj["choices"][0]["finish_reason"] or finish
    if not done:
        err = err or "stream ended without [DONE]"
    return 200, finish, None, err


def serve_requests(engine, slo):
    """Run the HTTP server on ``engine`` and answer N_REQUESTS
    concurrent clients.  Returns (results, wall seconds, the loop)."""
    from repro.launch import serve
    srv = serve.make_server(engine, slo, host="127.0.0.1", port=0,
                            tok_workers=2)
    th = threading.Thread(target=srv.run, name="frontend", daemon=True)
    th.start()
    try:
        check(srv.started.wait(timeout=120), "server did not start")
        prompts = make_prompts()
        results = [None] * N_REQUESTS

        def client(i):
            try:
                results[i] = post_completion(srv.port, prompts[i],
                                             stream=(i % 2 == 0))
            except OSError as e:
                results[i] = (0, None, None, repr(e))

        t0 = time.perf_counter()
        clients = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(N_REQUESTS)]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=CLIENT_TIMEOUT_S + 30)
        wall = time.perf_counter() - t0
        pids = set(srv.seen_worker_pids)
    finally:
        srv.shutdown()
        th.join(timeout=120)
    check(not th.is_alive(), "server did not shut down")
    check(pids and os.getpid() not in pids,
          f"tokenizer work did not run in worker processes: {pids}")
    return results, wall, srv.loop


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------

def served_steps_compiled(engine, horizon: int):
    """Compile (or load from the cache) the two served step programs at
    the largest shapes the server can ask for: every slot, a full block
    table, and for the mixed prefill+decode step a full P-heavy chunk;
    the decode horizon runs ``horizon`` fused steps."""
    import jax
    import jax.numpy as jnp
    from repro.engine.batching import bucket, bucket_batch
    ex = engine.cluster.instances[0].executor
    B, NB = bucket_batch(ex.n_slots), ex.kv.max_blocks
    T = bucket(engine.sc.sliders.s_p, ex.t_buckets)
    fact(served_step_shapes={"B": B, "T": T, "NB": NB, "K": horizon})
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    key = jax.random.PRNGKey(0)
    mixed = ex._mixed_fused.lower(
        engine.params, ex.kv.pool, i32(B, T), i32(B), i32(B), i32(B, NB),
        key).compile()
    fused = ex._horizon_paged.lower(
        engine.params, ex.kv.pool, i32(B), i32(B), i32(B), i32(B, NB),
        key, K=horizon).compile()
    return {"mixed": mixed, "horizon": fused}


def compare_logits(cfg, params):
    """Largest |kernel - jnp| logit difference over a two-chunk prefill
    and COMPARE_DECODE decode steps through the paged pool, relative to
    the largest |jnp| logit.  Returns (relative diff, absolute diff,
    kernel program text)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import attention
    from repro.models import transformer as tf
    bs = 16
    n_tok = 2 * COMPARE_CHUNK + COMPARE_DECODE
    n_blk = -(-n_tok // bs)
    tables = jnp.arange(n_blk, dtype=jnp.int32)[None]
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, n_tok).astype(np.int32)

    def run(kernels: bool):
        # the kernel switch is read while tracing: a fresh jit per path
        prev = attention._USE_KERNELS
        attention.use_kernels(kernels)
        try:
            @jax.jit
            def step(params, pool, tokens, positions):
                logits, pool, _ = tf.forward(
                    params, cfg, tokens, positions, pool,
                    block_tables=(tables, bs))
                return logits.astype(jnp.float32), pool

            pool = tf.init_paged_cache(cfg, n_blk, bs)
            outs, pos = [], 0
            for n in [COMPARE_CHUNK] * 2 + [1] * COMPARE_DECODE:
                toks = jnp.asarray(ids[None, pos:pos + n])
                positions = jnp.arange(pos, pos + n, dtype=jnp.int32)[None]
                logits, pool = step(params, pool, toks, positions)
                outs.append(np.asarray(logits[0]))
                pos += n
            text = step.lower(params, pool, toks, positions).as_text()
            return np.concatenate(outs), text
        finally:
            attention.use_kernels(prev)

    got, text = run(True)
    ref, _ = run(False)
    diff = float(np.max(np.abs(got - ref)))
    return diff / float(np.max(np.abs(ref))), diff, text


def main():
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch import serve
    cache = serve.enable_compile_cache()
    import jax
    from jax import monitoring

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    fact(device=device, compile_cache=cache)
    if dev.platform != "tpu":
        raise CheckFailed(f"no TPU: JAX's first device is {dev.platform}")
    from repro.core.hw import hardware_for
    from repro.core.latency import SLO
    from repro.kernels import resolve_interpret
    hw = hardware_for(dev.device_kind)
    fact(assumed_peaks={"name": hw.name, "peak_flops": hw.peak_flops,
                        "hbm_bw": hw.hbm_bw})
    check(not resolve_interpret(None),
          "Pallas kernels would run in the interpreter on the TPU "
          "(REPRO_KERNELS_INTERPRET?)")

    compile_s = {"compile_or_load_s": 0.0, "persistent_cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s["compile_or_load_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compile_s["persistent_cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    slo = SLO(ttft=5.0, tpot=0.5)        # loose: this run is about tokens
    t0 = time.perf_counter()
    engine = serve.build_engine(ARCH, slo, policy="taichi", horizon=HORIZON)
    stats = dev.memory_stats()
    fact(engine=engine.cfg.name, layers=engine.cfg.num_layers,
         d_model=engine.cfg.d_model, dtype=engine.cfg.dtype,
         kernels=engine.kernels, n_slots=engine.sc.n_slots,
         max_seq=engine.sc.max_ctx, hbm_blocks=engine.sc.hbm_blocks,
         kv_pool_bytes=engine.pool_bytes,
         bytes_in_use_after_build=stats.get("bytes_in_use"),
         bytes_limit=stats.get("bytes_limit"),
         build_s=time.perf_counter() - t0)
    check(engine.kernels, "paged kernels are off on a TPU backend")

    steps = {name: (c.memory_analysis().temp_size_in_bytes,
                    "tpu_custom_call" in c.as_text())
             for name, c in served_steps_compiled(engine, HORIZON).items()}
    fact(served_steps={name: {"temp_bytes": t, "tpu_custom_call": k}
                       for name, (t, k) in steps.items()})
    for name, (temp, has_kernel) in steps.items():
        check(has_kernel, f"compiled {name} step holds no tpu_custom_call")
        check(stats["bytes_in_use"] + temp <= stats["bytes_limit"],
              f"KV pools, weights and the {name} step's temporaries do "
              "not fit the device")

    results, wall, loop = serve_requests(engine, slo)
    cluster = engine.cluster
    fact(requests=[{"prompt_tokens": n, "stream": i % 2 == 0,
                    "status": r[0], "finish": r[1],
                    "completion_tokens": r[2], "error": r[3]}
                   for i, (n, r) in enumerate(zip(PROMPT_LENS, results))])
    served = [len(r.output_tokens) for r in loop.requests]
    fact(requests_wall_s=wall, served_tokens=served,
         transfers=cluster.transfer_count, exec_errors=cluster.exec_errors,
         jit_compiles=sum(i.executor.jit_compiles()
                          for i in cluster.instances),
         peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"),
         **compile_s)
    serve.exit_on_exec_errors(cluster)
    for i, r in enumerate(results):
        check(r is not None and r[0] == 200 and not r[3],
              f"request {i} failed: {r}")
        check(r[1] == "length", f"request {i} finished with {r[1]!r}")
        check(r[2] in (None, MAX_TOKENS),
              f"request {i} got {r[2]} tokens")
    check(served == [MAX_TOKENS] * N_REQUESTS,
          f"server-side token counts {served}")
    check(cluster.transfer_count >= 1, "no KV migration landed")

    rel, diff, text = compare_logits(engine.cfg, engine.params)
    fact(logits_kernel_vs_jnp={"max_abs_diff": diff, "rel_diff": rel,
                               "tolerance": LOGIT_TOL,
                               "kernel_tpu_custom_call":
                                   "tpu_custom_call" in text},
         peak_bytes_in_use=dev.memory_stats().get("peak_bytes_in_use"),
         **compile_s)
    check("tpu_custom_call" in text, "kernel logit path ran no kernel")
    check(rel <= LOGIT_TOL,
          f"kernel vs jnp logits differ by {rel} > {LOGIT_TOL}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except CheckFailed as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
