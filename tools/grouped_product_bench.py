"""One grouped matrix product (``jax.lax.ragged_dot``) alone, on the chip,
under the compiler's own tile and under candidates: the measurement that
``moe/held.py::grouped_tiling``'s constants were read from (PERF.md,
PR 34).

    chiprun -- python3 tools/grouped_product_bench.py [case ...]
    chiprun -- python3 tools/grouped_product_bench.py --sweep

Without ``--sweep``: the three expert cells' shapes at the token buckets
their ticks run (``CASES``), row tiles 8-256 x every weight block of
``blocks()``, or ``--tiles`` alone beside the rule's. With it: ALL of a layer's experts held, 16-1,024 rows a
group, row tiles 64-512 x the largest block the VMEM budget admits and
the widest one that keeps the contraction whole: where the compiler's tile
wins, if anywhere.
Group sizes are drawn as a tick draws them: every token picks its
``top_k`` of the router's experts by a lognormal popularity + Gumbel
noise, padding rows repeat one token's choice, the held experts' counts
are kept. A product's time is the best mean of ``--calls`` calls in
flight, ``--reps`` times over; every result is compared bit for bit with
the compiler's tile's. One JSON line a (shape, product, tile) is appended
to ``--out``; a table is printed. Needs a TPU: the CPU ignores the tile.
"""
import argparse
import json
import os
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.xla_metadata import set_xla_metadata

from paddle_tpu.incubate.distributed.models.moe.held import (
    TILE_VMEM_BUDGET, grouped_tiling, tile_vmem_bytes)

HBM_BYTES_A_S = 819e9          # v5e

# name: token bucket, real tokens, top_k, router's experts, held, sigma of
# the experts' log popularity, hidden size, expert size
CASES = {
    "ling512": (512, 509, 8, 512, 128, 0.6, 2560, 768),
    "ling128": (128, 120, 8, 512, 128, 0.6, 2560, 768),
    "giga512": (512, 512, 8, 256, 16, 0.08, 7168, 2048),
    "giga128": (128, 128, 8, 256, 16, 0.08, 7168, 2048),
    "giga16": (16, 16, 8, 256, 16, 0.08, 7168, 2048),
    "giga1": (1, 1, 8, 256, 16, 0.08, 7168, 2048),
    "small512": (512, 510, 6, 64, 64, 0.12, 2560, 768),
    "small32": (32, 24, 6, 64, 64, 0.12, 2560, 768),
    "small4": (4, 4, 6, 64, 64, 0.12, 2560, 768),
    # the deployment's load an expert: all 16 of 16 held
    "giga512_all16of16": (512, 512, 8, 16, 16, 0.08, 7168, 2048),
}
# --sweep: every expert of the layer held, (name, experts, top_k, h, m)
SWEEP_WIDTHS = (("giga", 16, 8, 7168, 2048), ("small", 64, 6, 2560, 768))
SWEEP_ROWS_A_GROUP = (16, 64, 128, 256, 512, 1024)


def draw_sizes(rng, bucket, real, top_k, experts, held, sigma):
    """Rows each held expert gets in one tick."""
    logp = rng.normal(0.0, sigma, experts)
    noise = rng.gumbel(size=(real, experts))
    pick = np.argsort(-(logp[None] + noise), axis=1)[:, :top_k]
    if bucket > real:
        pick = np.concatenate([pick, np.repeat(pick[:1], bucket - real, 0)])
    flat = pick.reshape(-1)
    return np.bincount(flat[flat < held],
                       minlength=held)[:held].astype(np.int32)


def product(tiling, out_dtype):
    def fn(x, w, s):
        if tiling is None:
            return jax.lax.ragged_dot(x, w, s,
                                      preferred_element_type=out_dtype)
        with set_xla_metadata(ragged_dot_tiling="%d,%d,%d" % tiling):
            return jax.lax.ragged_dot(x, w, s,
                                      preferred_element_type=out_dtype)
    return jax.jit(fn)


def timed(fn, args, calls, reps):
    out = fn(*args)
    out.block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3, out


def blocks(k, n):
    """Weight blocks ``(tk, tn)``: the compiler's 512 x 256 / 512, and
    larger ones that divide K and N, up to ~4 MiB a block."""
    tks = [d for d in (256, 512, 640, 768, 1024, 1280, 1792, 2048, 2560,
                       3584, 7168) if k % d == 0]
    tns = [d for d in (256, 384, 512, 768, 896, 1024, 1280, 1792, 2048,
                       2560, 3584) if n % d == 0]
    least = 0.75e6 if k * n * 2 < 5e6 else 1.5e6
    return [(tk, tn) for tk in tks for tn in tns
            if (tk, tn) in ((512, 256), (512, 512), (256, 512))
            or least <= tk * tn * 2 <= 4.3e6]


def largest_block(tm, k, n, out_itemsize, whole_k=False):
    """The largest block inside the rule's VMEM budget at row tile
    ``tm``, or the widest one that keeps the contraction whole (what
    ``grouped_tiling`` picks, for any ``tm``); None where none fits."""
    fits = [(tk * tn, tk, tn)
            for tk in range(128, k + 1, 128) if k % tk == 0
            for tn in range(128, n + 1, 128) if n % tn == 0
            if tile_vmem_bytes(tm, tk, tn, 2, out_itemsize)
            + (tm * tn * 4 if tk < k else 0)    # a split one's accumulator
            <= TILE_VMEM_BUDGET and (tk == k or not whole_k)]
    return max(fits)[1:] if fits else None


def measure(name, sizes, rows, k, n, out_dtype, tiles, rule, args, sink):
    """One product under the compiler's tile and each of ``tiles``."""
    held = len(sizes)
    out_itemsize = jnp.dtype(out_dtype or jnp.bfloat16).itemsize
    nonempty, total = int((sizes > 0).sum()), int(sizes.sum())
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (rows, k), jnp.bfloat16)
    w = jax.random.normal(key, (held, k, n), jnp.bfloat16) * 0.02
    s = jnp.asarray(sizes)
    floor_ms = (nonempty * k * n * 2
                + total * (k * 2 + n * out_itemsize)) / HBM_BYTES_A_S * 1e3
    rec = dict(case=name, rows=rows, k=k, n=n, groups=held,
               nonempty=nonempty, held_rows=total, max_rows=int(sizes.max()),
               floor_ms=floor_ms, rule=rule)
    # calls in flight hold their results: keep those under ~2 GB
    calls = max(4, min(args.calls, int(2e9 // (rows * n * out_itemsize))))
    own_ms, ref = timed(product(None, out_dtype), (x, w, s), calls,
                        args.reps)
    ref = np.asarray(ref[:total].astype(jnp.float32))
    sink(dict(rec, tiling=None, ms=own_ms))
    print(f"{name:20s} [{rows},{k}]x[{held},{k},{n}] held rows {total} "
          f"in {nonempty} groups, busiest {int(sizes.max())}; floor "
          f"{floor_ms:.3f} ms; compiler's tile {own_ms:.3f} ms; the rule: "
          f"{rule}", flush=True)
    got = []
    for tile in tiles:
        try:
            ms, out = timed(product(tile, out_dtype), (x, w, s), calls,
                            args.reps)
            equal = bool(np.array_equal(
                np.asarray(out[:total].astype(jnp.float32)), ref))
            sink(dict(rec, tiling=tile, ms=ms, equal=equal))
            got.append((ms, tile, equal))
        except Exception as e:          # the compiler's VMEM refusal
            sink(dict(rec, tiling=tile, ms=None,
                      err=str(e)[-160:].replace("\n", " ")))
    for ms, tile, equal in sorted(got)[:args.show]:
        print(f"    {tile!s:20s} {ms:.3f} ms  ({ms / own_ms:.2f} of the "
              f"compiler's)  bits_equal={equal}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", help=f"of {sorted(CASES)}, or with "
                    f"--sweep of {[w[0] for w in SWEEP_WIDTHS]}")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--tiles", nargs="+", metavar="TM,TK,TN", help="with "
                    "cases: these tiles (where they divide the shape) and "
                    "the rule's, instead of the grid")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--show", type=int, default=8)
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "grouped_product_bench.jsonl"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu":
        sys.exit("needs a TPU: another backend ignores ragged_dot_tiling")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        def sink(rec):
            f.write(json.dumps(rec) + "\n")
            f.flush()

        if args.sweep:
            for name, experts, top_k, h, m in SWEEP_WIDTHS:
                if args.cases and name not in args.cases:
                    continue
                for rows_a_group in SWEEP_ROWS_A_GROUP:
                    rows = rows_a_group * experts
                    tokens = rows // top_k      # the buffer's tail: no group's
                    case = f"{name}_all_{rows_a_group}"
                    sizes = draw_sizes(
                        np.random.default_rng(zlib.crc32(case.encode())),
                        tokens, tokens, top_k, experts, experts, 0.1)
                    for k, n, out_dtype, out_b in ((h, m, None, 2),
                                                   (m, h, jnp.float32, 4)):
                        tiles = []
                        for tm in (64, 128, 256, 512):
                            for whole_k in (False, True):
                                blk = largest_block(tm, k, n, out_b, whole_k)
                                if (blk and rows % tm == 0
                                        and (tm, *blk) not in tiles):
                                    tiles.append((tm, *blk))
                        rule = grouped_tiling(rows, k, n, 2, out_b)
                        measure(case, sizes, rows, k, n, out_dtype, tiles,
                                rule, args, sink)
            return
        for name in args.cases or CASES:
            bucket, real, top_k, experts, held, sigma, h, m = CASES[name]
            sizes = draw_sizes(
                np.random.default_rng(zlib.crc32(name.encode())),
                bucket, real, top_k, experts, held, sigma)
            rows = bucket * top_k
            for k, n, out_dtype, out_b in ((h, m, None, 2),
                                           (m, h, jnp.float32, 4)):
                rule = grouped_tiling(rows, k, n, 2, out_b)
                tiles = [(tm, tk, tn) for tm in (8, 16, 32, 64, 128, 256)
                         if rows % tm == 0 for tk, tn in blocks(k, n)]
                if args.tiles:
                    given = [tuple(map(int, t.split(",")))
                             for t in args.tiles]
                    tiles = [rule] * bool(rule) + [
                        t for t in given if t != rule and rows % t[0] == 0
                        and k % t[1] == 0 and n % t[2] == 0]
                measure(name, sizes, rows, k, n, out_dtype, tiles, rule,
                        args, sink)


if __name__ == "__main__":
    main()
