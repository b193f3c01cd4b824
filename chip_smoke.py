#!/usr/bin/env python3
"""Smoke test of the serve path on a TPU: the quickest proof that the
system still starts on the chip. It is a smoke, not a benchmark.

  python chip_smoke.py           # one chip
  python chip_smoke.py --tp 4    # a four-chip host: tensor parallelism only

One chip, in one process:
  (a) device: JAX's first device must be a TPU;
  (b) kernel: the paged-attention kernel at qwen2_7b widths (28 query
      heads over 4 KV heads, d_head 128, bf16) against the float32
      oracle ``kernels.ref.attention_ref``, compiled by Mosaic;
  (c) serve: qwen2_7b at its published widths, cut to 14 layers, serves
      8 requests (2 families sharing a 256-token prefix) through
      ``repro.launch.serve``'s own builder: paged engine, LERC prefix
      store, fcfs scheduler. Every request must finish with its tokens,
      the store must record effective hits, the compiled step must hold
      the Mosaic kernel (``tpu_custom_call``), and the KV pool, sized to
      the chip, must never grow (a growth would also recompile the step).

``--tp 4`` runs only the tensor-parallel path and what it is compared
with: the same requests at tp=1 on device 0, then at tp=4, and the two
generations must be token-identical.

Compile seconds and step wall times are printed as diagnostics. The last
line of stdout is one JSON object: ``{"ok": true, "device": {...}}``. Any
failure exits non-zero and prints no such line; so does a run that finds
no TPU, or a directory that holds this script without the repository.
"""
from __future__ import annotations

import argparse
import gc
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

LAYERS = 14
BLOCK_TOKENS = 16
POOL_ROWS = 8192          # 448 KiB a row at 14 layers: 3.5 GiB of KV
SLOTS = 4
MAX_SEQ = 512
MAX_NEW = 16
# Tolerance of the kernel check, |out - ref| <= ATOL + RTOL * |ref|. The
# output is rounded to bf16 (2^-9 relative), and the MXU may multiply f32
# operands in one bf16 pass, rounding q*scale and the softmax weights to 8
# mantissa bits: about 1.5% of each weight, so ~1.5% of the mean |v| the
# weights average (~1 here). A wrong page, head or mask bit moves an
# output by the size of a value row (~1) at the short contexts the check
# includes.
KERNEL_ATOL = 3e-2
KERNEL_RTOL = 3e-2

def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def check_device(need: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU: JAX's first device is {d.platform!r}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if len(devs) < need:
        fail(f"--tp {need} needs {need} chips, JAX sees {len(devs)}")
    return d, len(devs)


def kernel_phase() -> None:
    """The serve path's kernel entry point, on the chip, against the
    float32 oracle over the logical cache each block table describes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import paged_decode_attention
    from repro.kernels.ref import attention_ref

    B, H, KV, D, bt, NW, NB = 4, 28, 4, 128, BLOCK_TOKENS, 32, 256
    L = NW * bt
    for S in (1, 8):
        ks = jax.random.split(jax.random.key(S), 4)
        q = jax.random.normal(ks[0], (B, S, H, D)).astype(jnp.bfloat16)
        kp = jax.random.normal(ks[1], (NB, bt, KV, D)).astype(jnp.bfloat16)
        vp = jax.random.normal(ks[2], (NB, bt, KV, D)).astype(jnp.bfloat16)
        # disjoint shuffled tables: pool row order is unrelated to position
        tables = jax.random.permutation(ks[3], NB)[:B * NW] \
            .reshape(B, NW).astype(jnp.int32)
        # first position, mid-block, a block edge, the table's end
        pos0 = np.array([0, 37, 8 * bt - 1, L - S], np.int32)
        qpos = jnp.asarray(pos0[:, None] + np.arange(S)[None, :])
        hlo = paged_decode_attention.lower(
            q, kp, vp, tables, qpos).compile().as_text()
        if "tpu_custom_call" not in hlo:
            fail("paged kernel did not compile to a Mosaic custom call")
        out = np.asarray(paged_decode_attention(q, kp, vp, tables, qpos),
                         np.float32)
        err, worst = 0.0, 0.0
        with jax.default_matmul_precision("highest"):
            for b in range(B):
                kc = kp[tables[b]].reshape(1, L, KV, D).astype(jnp.float32)
                vc = vp[tables[b]].reshape(1, L, KV, D).astype(jnp.float32)
                # queries placed at their absolute positions of a causal
                # pass over the whole logical cache
                qf = jnp.zeros((1, L, H, D), jnp.float32).at[
                    0, pos0[b]:pos0[b] + S].set(q[b].astype(jnp.float32))
                ref = np.asarray(attention_ref(qf, kc, vc, causal=True))
                ref = ref[0, pos0[b]:pos0[b] + S]
                diff = np.abs(out[b] - ref)
                err = max(err, float(np.max(diff)))
                worst = max(worst, float(np.max(
                    diff / (KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)))))
        print(f"kernel: S={S} bt={bt} max_abs_err={err!r} "
              f"err_over_tol={worst!r} (atol={KERNEL_ATOL} "
              f"rtol={KERNEL_RTOL})")
        if not np.isfinite(worst) or worst > 1.0:
            fail(f"paged kernel error {err} past tolerance at S={S}")


def serve_phase(tp: int) -> list:
    """Serve the smoke's requests through ``repro.launch.serve``'s
    builder at ``tp``; check them and return every request's tokens."""
    import jax
    import numpy as np
    from jax import monitoring
    from repro import configs
    from repro.launch.serve import build_server
    from repro.serve.kv_pool import chain_block_nbytes
    from repro.models import init_decode_cache

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)

    argv = ["--arch", "qwen2_7b", "--layers", str(LAYERS),
            "--policy", "lerc", "--scheduler", "fcfs", "--paged-attention",
            "--requests", "8", "--slots", str(SLOTS),
            "--shared-prefix", "256", "--max-new", str(MAX_NEW),
            "--max-seq", str(MAX_SEQ), "--block-tokens", str(BLOCK_TOKENS),
            "--pool-blocks", str(POOL_ROWS), "--tp", str(tp)]
    # the store may own every row the slots' block tables cannot claim
    cfg = configs.get("qwen2_7b").replace(n_layers=LAYERS)
    row_bytes = chain_block_nbytes(init_decode_cache(cfg, 1, 8),
                                   BLOCK_TOKENS)
    free_rows = POOL_ROWS - SLOTS * (MAX_SEQ // BLOCK_TOKENS) - 1
    cache_kb = free_rows * row_bytes // 1024
    t0 = time.perf_counter()
    srv = build_server(argv + ["--cache-kb", str(cache_kb)])
    eng = srv.engine
    jax.block_until_ready(eng.params)
    build_s = time.perf_counter() - t0
    print(f"serve[tp={tp}]: arch={srv.cfg.arch} layers={srv.cfg.n_layers} "
          f"d_model={srv.cfg.d_model} heads={srv.cfg.n_heads}/"
          f"{srv.cfg.kv_heads} d_ff={srv.cfg.d_ff} vocab={srv.cfg.vocab} "
          f"pool_rows={eng.pool.num_blocks} row_bytes={row_bytes} "
          f"cache_kb={cache_kb} build_s={build_s!r} "
          f"build_compiles={len(compiles)} "
          f"build_compile_s={sum(compiles)!r}")

    reqs = [eng.submit(p, max_new=MAX_NEW) for p in srv.prompts]
    n_compiles = len(compiles)
    steady, compiling = [], 0.0
    t_run = time.perf_counter()
    while eng.queue or any(s is not None for s in eng.slots):
        n0 = len(compiles)
        t = time.perf_counter()
        eng.step()
        jax.block_until_ready(eng.pool.buffers)
        dt = time.perf_counter() - t
        if len(compiles) > n0:
            compiling += dt
        else:
            steady.append(dt)
        if eng.steps > 10_000:
            fail("serve loop did not drain")
    run_s = time.perf_counter() - t_run
    m = eng.metrics()
    step_compiles = compiles[n_compiles:]
    print(f"serve[tp={tp}]: steps={eng.steps} run_s={run_s!r} "
          f"step_compiles={len(step_compiles)} "
          f"compile_s={sum(step_compiles)!r} "
          f"compiling_steps_wall_s={compiling!r} "
          f"steady_step_median_s={statistics.median(steady)!r} "
          f"steady_step_max_s={max(steady)!r}")
    print(f"serve[tp={tp}]: effective_hits={m['effective_hits']} "
          f"hits={m['hits']} accesses={m['accesses']} "
          f"prefill_saved_frac={m['prefill_saved_frac']!r} "
          f"pool_blocks={m['pool_blocks']} "
          f"pool_high_water={m['pool_high_water']}")

    tokens = [list(r.generated) for r in reqs]
    vocab = srv.cfg.vocab
    for r, toks in zip(reqs, tokens):
        if not r.done or r.cancelled or len(toks) != MAX_NEW:
            fail(f"request {r.rid} finished={r.done} with "
                 f"{len(toks)}/{MAX_NEW} tokens")
        if not all(0 <= t < vocab for t in toks):
            fail(f"request {r.rid} emitted a token outside the vocabulary")
    print(f"serve[tp={tp}]: request 1 tokens {tokens[0]}")
    if m["effective_hits"] <= 0:
        fail("the prefix store recorded no effective hit")
    if eng.pool.num_blocks != POOL_ROWS or eng.pool.grows:
        fail(f"KV pool grew to {eng.pool.num_blocks} rows "
             f"({eng.pool.grows} growths)")
    if "tpu_custom_call" not in eng.step_hlo():
        fail("the compiled serve step holds no Mosaic kernel")
    if tp > 1 and eng.tp != tp:
        fail(f"engine runs tp={eng.tp}, asked for {tp}")
    eng.close()
    return tokens


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=1, choices=[1, 4],
                    help="4: compare tensor-parallel generations with tp=1 "
                         "on a four-chip host, and nothing else")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repository beside this script ({ROOT / 'src' / 'repro'})")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    dev, count = check_device(args.tp)
    warm = Path(cache).is_dir() and any(Path(cache).iterdir())
    print(f"compile cache: {cache} ({'warm' if warm else 'cold'})")

    if args.tp == 1:
        kernel_phase()
        serve_phase(1)
    else:
        import jax
        ref = serve_phase(1)
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays())
        print(f"tp compare: {live} bytes live on device after tp=1")
        got = serve_phase(args.tp)
        if got != ref:
            bad = [i for i, (a, b) in enumerate(zip(ref, got)) if a != b]
            fail(f"tp={args.tp} generations differ from tp=1 on requests "
                 f"{bad}")
        print(f"tp compare: tp={args.tp} tokens identical to tp=1 on "
              f"{len(ref)} requests")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
