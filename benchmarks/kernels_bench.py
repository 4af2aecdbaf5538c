"""Kernel micro-benchmarks (interpret mode on CPU; wall time is the CPU
emulation, the derived column carries the TPU-relevant byte/FLOP counts).

Two datapaths at the paper's lg-2400 scale (B=1024, F=16, T=200, m=2400):

* float: every bit is a float32 — thermometer -> one-hot-matmul LUT eval
  -> popcount, staged through HBM, plus the float fused kernel;
* packed: every bit lives in uint32 words (32/word) — packed encode ->
  shift/AND LUT eval -> SWAR popcount — plus the served fused kernel
  (``fused_dwn_batch_major``), which never writes a bit tensor out.

Timings (warmed, so compile time is excluded) and the packed-vs-float
speedups are written to ``BENCH_kernels.json`` at the repo root (one
record per run, overwritten).

``--smoke-bm`` runs the batch-major bit-exactness smoke instead (all
three JSC preset widths + a ragged batch), used as a fast CI gate.
"""

import json
import sys

from .common import csv_row, Timer, ROOT

BENCH_JSON = ROOT / "BENCH_kernels.json"


def _timed(fn):
    """(us, result) of one warmed call: run once to compile, then time."""
    import jax
    jax.block_until_ready(fn())
    with Timer() as t:
        out = fn()
        jax.block_until_ready(out)
    return t.us, out


def smoke_bm():
    """Batch-major bit-exactness smoke: all three JSC preset LUT widths
    (plus a ragged, non-power-of-two batch) against the packed oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.autotune import FusedConfig
    from repro.kernels.fused import ops as f_ops

    F, T, n, C = 16, 200, 6, 5
    for m, B in ((50, 64), (360, 37), (2400, 128)):
        key = jax.random.PRNGKey(m)
        kx, kt, km, kl = jax.random.split(key, 4)
        x = jax.random.uniform(kx, (B, F), minval=-1, maxval=1)
        th = jnp.sort(jax.random.uniform(kt, (F, T), minval=-1, maxval=1), 1)
        mapping = jax.random.randint(km, (m, n), 0, F * T)
        tables = jax.random.randint(kl, (m, 64), 0, 2).astype(jnp.int32)
        ref_counts, ref_idx = f_ops.fused_dwn_packed_ref(
            x, th, [mapping], [tables], C)
        counts, idx = f_ops.forward_packed(
            x, th, mapping, tables, C, interpret=True,
            config=FusedConfig(block_b=64))
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts))
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        print(f"smoke-bm ok: m={m} B={B}")
    print("batch-major bit-exact on all preset widths")


def run():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.thermometer import ops as th_ops
    from repro.kernels.lut_eval import ops as lut_ops
    from repro.kernels.popcount import ops as pc_ops
    from repro.kernels.fused import ops as f_ops

    B, F, T, m, n, C = 1024, 16, 200, 2400, 6, 5
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(key, (B, F), minval=-1, maxval=1)
    th = jnp.sort(jax.random.uniform(key, (F, T), minval=-1, maxval=1), 1)
    mapping = jax.random.randint(key, (m, n), 0, F * T)
    tables_f = jax.random.randint(key, (m, 64), 0, 2).astype(jnp.float32)
    tables_i = tables_f.astype(jnp.int32)

    # ---- float staged pipeline ------------------------------------------
    t_enc, bits = _timed(lambda: th_ops.encode(x, th, interpret=True))
    t_lut, out = _timed(lambda: lut_ops.evaluate(bits, mapping, tables_f,
                                                 interpret=True))
    t_pop, counts = _timed(lambda: pc_ops.classify(out, C,
                                                   interpret=True)[0])
    t_fused_f, fused_f = _timed(lambda: f_ops.forward(x, th, mapping,
                                                      tables_f, C,
                                                      interpret=True))
    np.testing.assert_allclose(np.asarray(fused_f[0]), np.asarray(counts),
                               atol=1e-4)

    # ---- packed pipeline -------------------------------------------------
    t_enc_p, pwords = _timed(
        lambda: th_ops.encode_packed(x, th, interpret=True).words)
    from repro.core.bitpack import PackedBits
    packed = PackedBits(pwords, F * T)
    t_lut_p, powords = _timed(lambda: lut_ops.evaluate_packed(
        packed, mapping, tables_i, interpret=True).words)
    pout = PackedBits(powords, m)
    t_pop_p, _ = _timed(lambda: pc_ops.classify_packed(pout, C,
                                                       interpret=True)[0])
    t_fused_p, fused_p = _timed(lambda: f_ops.forward_packed(
        x, th, mapping, tables_i, C, interpret=True)[0])
    np.testing.assert_array_equal(np.asarray(fused_p), np.asarray(counts))

    # ---- HBM traffic model ----------------------------------------------
    # float staged writes + re-reads the unary blow-up at 4 B/bit; packed
    # moves the identical bits at 1/32 B/bit; fused keeps them in VMEM.
    bits_f32 = B * F * T * 4
    bits_pack = B * F * T // 8
    staged_f = (B * F * 4 + 2 * bits_f32 + m * 64 * 4 + B * m * 4 * 2
                + B * C * 4)
    staged_p = (B * F * 4 + 2 * bits_pack + m * 64 * 4 + B * (m // 8) * 2
                + B * C * 4)
    fused_b = B * F * 4 + m * 64 * 4 + B * C * 4

    staged_total_f = t_enc + t_lut + t_pop
    csv_row("kernels/thermometer", t_enc, f"bits_bytes={bits_f32}")
    csv_row("kernels/thermometer_packed", t_enc_p,
            f"bits_bytes={bits_pack};vs_float={t_enc / t_enc_p:.1f}x")
    csv_row("kernels/lut_eval", t_lut, f"m={m}")
    csv_row("kernels/lut_eval_packed", t_lut_p,
            f"m={m};vs_float={t_lut / t_lut_p:.1f}x")
    csv_row("kernels/popcount", t_pop, f"classes={C}")
    csv_row("kernels/popcount_packed", t_pop_p,
            f"classes={C};vs_float={t_pop / t_pop_p:.1f}x")
    csv_row("kernels/fused", t_fused_f,
            f"staged_hbm={staged_f};fused_hbm={fused_b};"
            f"saving={staged_f / fused_b:.1f}x")
    csv_row("kernels/fused_packed", t_fused_p,
            f"vs_float_staged={staged_total_f / t_fused_p:.1f}x;"
            f"vs_float_fused={t_fused_f / t_fused_p:.1f}x")

    record = {
        "scale": {"B": B, "F": F, "T": T, "m": m, "classes": C},
        "float_us": {"encode": round(t_enc, 1), "lut_eval": round(t_lut, 1),
                     "popcount": round(t_pop, 1),
                     "staged_total": round(staged_total_f, 1),
                     "fused": round(t_fused_f, 1)},
        "packed_us": {"encode": round(t_enc_p, 1),
                      "lut_eval": round(t_lut_p, 1),
                      "popcount": round(t_pop_p, 1),
                      "fused": round(t_fused_p, 1)},
        "speedup": {
            "fused_packed_vs_float_staged":
                round(staged_total_f / t_fused_p, 2),
            "fused_packed_vs_float_fused": round(t_fused_f / t_fused_p, 2),
            "encode_packed_vs_float": round(t_enc / t_enc_p, 2),
        },
        "hbm_model_bytes": {"float_staged": staged_f,
                            "packed_staged": staged_p, "fused": fused_b},
        "bit_exact": True,
    }
    with open(BENCH_JSON, "w") as fh:
        json.dump(record, fh, indent=2)
    print(f"\npacked fused vs float staged pipeline: "
          f"{staged_total_f / t_fused_p:.1f}x wall-clock "
          f"({staged_total_f / 1e3:.1f} ms -> {t_fused_p / 1e3:.2f} ms per "
          f"{B}-sample batch); bit widths: {bits_f32 / 1e6:.1f} MB float "
          f"-> {bits_pack / 1e6:.2f} MB packed; written {BENCH_JSON.name}")


if __name__ == "__main__":
    if "--smoke-bm" in sys.argv[1:]:
        smoke_bm()
    else:
        run()
