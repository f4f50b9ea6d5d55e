"""Pallas kernel benchmarks on the live backend: matmul + flash attention.

Times `tpu_dist.ops.matmul` (fused-epilogue Pallas kernel) against XLA's
`jnp.dot`, and `tpu_dist.ops.flash_attention` against the dense XLA
attention (`tpu_dist.nn.attention.dense_attention`), forward and
forward+backward.  Reports ms and achieved TFLOP/s per case, then one
JSON line for machines.

Run it on the real chip — ``python benchmarks/kernels.py`` — or exercise
the harness on CPU with ``--platform cpu`` (interpret mode, math only,
timings meaningless).  ``chip_smoke.py`` holds the correctness check of
the same kernels against their references.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


from tpu_dist.utils.timing import bench_chain  # chained in-program timing


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--mm-sizes", type=int, nargs="+", default=[1024, 2048, 4096])
    ap.add_argument("--seqs", type=int, nargs="+", default=[1024, 2048, 4096])
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument(
        "--tune", action="store_true",
        help="sweep matmul block configs per size and report the best "
        "(run on real hardware; interpret-mode timings are meaningless)",
    )
    args = ap.parse_args()
    from tpu_dist.utils.platform import select_platform

    select_platform(args.platform)
    # --platform cpu is the mechanics smoke: Pallas in interpret mode
    interpret = args.platform == "cpu"

    import jax
    import jax.numpy as jnp

    from tpu_dist import nn, ops

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})", file=sys.stderr)
    results = {"platform": dev.platform, "matmul": [], "attention": []}

    # ---- matmul: Pallas fused bias+relu vs XLA dot (+ the same epilogue) ----
    key = jax.random.key(0)
    for n in args.mm_sizes:
        k1, k2, k3, key = jax.random.split(key, 4)
        x = jax.random.normal(k1, (n, n), jnp.bfloat16)
        w = jax.random.normal(k2, (n, n), jnp.bfloat16)
        b = jax.random.normal(k3, (n,), jnp.bfloat16)
        flops = 2 * n * n * n

        # Both chains carry y -> clip(epilogue(y @ w + b)) so iterates stay
        # bounded in bf16; the clip is identical on both sides (negligible
        # next to the n^3 matmul).
        def pallas_step(y, _w=w, _b=b):
            return jnp.clip(
                ops.matmul(y, _w, _b, epilogue="relu", interpret=interpret), 0.0, 1.0
            )

        def xla_step(y, _w=w, _b=b):
            return jnp.clip(
                jnp.maximum(
                    jnp.dot(y, _w, preferred_element_type=jnp.float32)
                    + _b.astype(jnp.float32),
                    0.0,
                ).astype(jnp.bfloat16),
                0.0,
                1.0,
            )

        tp = bench_chain(pallas_step, x, iters=args.iters)
        tx = bench_chain(xla_step, x, iters=args.iters)
        row = {
            "n": n,
            "pallas_ms": round(tp * 1e3, 3),
            "xla_ms": round(tx * 1e3, 3),
            "pallas_tflops": round(flops / tp / 1e12, 2),
            "xla_tflops": round(flops / tx / 1e12, 2),
        }
        if args.tune and not interpret:
            # Block-config sweep: the auto pick (`ops.matmul` default) is
            # a heuristic; on hardware, measure the candidates and record
            # the winner so the default can be re-tuned from data.
            best = None
            for bm, bn, bk in (
                (256, 256, 512), (512, 512, 512), (512, 512, 1024),
                (512, 1024, 512), (1024, 512, 512), (256, 512, 1024),
                (512, 256, 1024), (1024, 1024, 512),
            ):
                if n % bm or n % bn or n % bk:
                    continue

                def tuned_step(y, _w=w, _b=b, bm=bm, bn=bn, bk=bk):
                    return jnp.clip(
                        ops.matmul(
                            y, _w, _b, epilogue="relu",
                            bm=bm, bn=bn, bk=bk, interpret=interpret,
                        ),
                        0.0, 1.0,
                    )

                try:
                    t = bench_chain(
                        tuned_step, x, iters=max(args.iters // 2, 5)
                    )
                except Exception as e:
                    print(
                        f"  tune {bm}x{bn}x{bk}: failed {e}",
                        file=sys.stderr,
                    )
                    continue
                print(
                    f"  tune {bm}x{bn}x{bk}: {t * 1e3:.3f}ms "
                    f"({flops / t / 1e12:.1f} TF/s)",
                    file=sys.stderr,
                )
                if best is None or t < best[1]:
                    best = ((bm, bn, bk), t)
            if best is not None:
                row["tuned_blocks"] = list(best[0])
                row["tuned_ms"] = round(best[1] * 1e3, 3)
                row["tuned_tflops"] = round(flops / best[1] / 1e12, 2)
        results["matmul"].append(row)
        print(
            f"matmul {n}x{n}x{n} bf16+relu: pallas {row['pallas_ms']}ms "
            f"({row['pallas_tflops']} TF/s)  xla {row['xla_ms']}ms "
            f"({row['xla_tflops']} TF/s)"
            + (
                f"  tuned {row['tuned_ms']}ms ({row['tuned_tflops']} TF/s) "
                f"@ {row['tuned_blocks']}"
                if "tuned_blocks" in row
                else ""
            ),
            file=sys.stderr,
        )

    # ---- flash attention vs dense XLA attention, fwd and fwd+bwd ----
    for S in args.seqs:
        kq, kk, kv, key = jax.random.split(key, 4)
        shape = (args.heads, S, args.dim)
        q = jax.random.normal(kq, shape, jnp.bfloat16)
        k = jax.random.normal(kk, shape, jnp.bfloat16)
        v = jax.random.normal(kv, shape, jnp.bfloat16)
        from tpu_dist.train.flops import attention_flops

        # causal-realizable FLOPs (≈half the dense 4·h·S²·d count)
        flops = attention_flops(
            1, args.heads, S, S, args.dim, causal=True
        )

        def flash_step(qc, _k=k, _v=v):
            return ops.flash_attention(qc, _k, _v, causal=True, interpret=interpret)

        def dense_step(qc, _k=k, _v=v):
            return nn.attention.dense_attention(qc, _k, _v, causal=True)

        def loss_flash(qc, _k=k, _v=v):
            return (
                ops.flash_attention(qc, _k, _v, causal=True, interpret=interpret)
                .astype(jnp.float32)
                .sum()
            )

        def loss_dense(qc, _k=k, _v=v):
            return (
                nn.attention.dense_attention(qc, _k, _v, causal=True)
                .astype(jnp.float32)
                .sum()
            )

        # fwd+bwd chains carry clip(dq + dk + dv) — all three grads feed
        # the carry so no part of the backward can be dead-code-eliminated.
        def flash_grad_step(qc):
            gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(qc, k, v)
            return jnp.clip(gq + gk + gv, -1.0, 1.0)

        def dense_grad_step(qc):
            gq, gk, gv = jax.grad(loss_dense, argnums=(0, 1, 2))(qc, k, v)
            return jnp.clip(gq + gk + gv, -1.0, 1.0)

        tf_ = bench_chain(flash_step, q, iters=args.iters)
        td = bench_chain(dense_step, q, iters=args.iters)
        tfg = bench_chain(flash_grad_step, q, iters=max(args.iters // 2, 3))
        tdg = bench_chain(dense_grad_step, q, iters=max(args.iters // 2, 3))
        row = {
            "seq": S,
            "flash_fwd_ms": round(tf_ * 1e3, 3),
            "dense_fwd_ms": round(td * 1e3, 3),
            "flash_fwdbwd_ms": round(tfg * 1e3, 3),
            "dense_fwdbwd_ms": round(tdg * 1e3, 3),
            "flash_fwd_tflops": round(flops / tf_ / 1e12, 2),
            "dense_fwd_tflops": round(flops / td / 1e12, 2),
        }
        if args.tune and not interpret:
            best = None
            for bq, bk in (
                (128, 128), (256, 256), (512, 512), (256, 512), (512, 256),
                (1024, 512),
            ):
                if S % bq or S % bk or bq > S or bk > S:
                    continue

                def tuned(qc, _k=k, _v=v, bq=bq, bk=bk):
                    return ops.flash_attention(
                        qc, _k, _v, causal=True, bq=bq, bk=bk,
                        interpret=interpret,
                    )

                try:
                    t = bench_chain(tuned, q, iters=max(args.iters // 2, 5))
                except Exception as e:
                    print(f"  tune bq{bq}/bk{bk}: failed {e}", file=sys.stderr)
                    continue
                print(
                    f"  tune bq{bq}/bk{bk}: {t * 1e3:.3f}ms "
                    f"({flops / t / 1e12:.1f} TF/s)",
                    file=sys.stderr,
                )
                if best is None or t < best[1]:
                    best = ((bq, bk), t)
            if best is not None:
                row["tuned_blocks"] = list(best[0])
                row["tuned_fwd_ms"] = round(best[1] * 1e3, 3)
                row["tuned_fwd_tflops"] = round(flops / best[1] / 1e12, 2)
        results["attention"].append(row)
        print(
            f"attn h{args.heads} S{S} d{args.dim} causal bf16: "
            f"flash fwd {row['flash_fwd_ms']}ms vs dense {row['dense_fwd_ms']}ms; "
            f"fwd+bwd {row['flash_fwdbwd_ms']}ms vs {row['dense_fwdbwd_ms']}ms",
            file=sys.stderr,
        )

    # Physical sanity: no kernel can beat the chip's peak FLOP rate.
    # Flag any such row so a broken timing can never be read as a result.
    from tpu_dist.train.flops import peak_flops

    peak = peak_flops(dev)
    if peak:
        peak_tf = peak / 1e12
        for row in results["matmul"]:
            for f in ("pallas_tflops", "xla_tflops", "tuned_tflops"):
                if row.get(f) and row[f] > peak_tf:
                    row["suspect"] = True
        for row in results["attention"]:
            for f in ("flash_fwd_tflops", "dense_fwd_tflops",
                      "tuned_fwd_tflops"):
                if row.get(f) and row[f] > peak_tf:
                    row["suspect"] = True
        results["peak_tflops"] = round(peak_tf, 1)
        if any(
            r.get("suspect")
            for r in results["matmul"] + results["attention"]
        ):
            print(
                "WARNING: rows exceeding the chip's physical peak are "
                "marked suspect — timings untrustworthy",
                file=sys.stderr,
            )
    print(json.dumps(results))


if __name__ == "__main__":
    main()
