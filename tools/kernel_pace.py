"""The paged-attention kernel ALONE at a serving cell's shape, whole and
with parts REMOVED: what a tick's attention calls take on the chip when
nothing else of the step runs, and which of copies and products the
time is.

The removal method (docs/performance.md, "A kernel alone, with parts
removed"): run the kernel as it is, then with one part patched out of
the TRACE (`make_async_copy` handing back a copy that does nothing,
`lax.switch` handing its carry through), and read what the whole pays
beyond each.  Results of a run with a part removed are WRONG and its
timing true.  Parts that overlap do not add up to the whole; parts in
one instruction stream do.  The WAITS alone cannot be removed: the
chip's runtime halts a Mosaic kernel that ends with a semaphore counted
up (PERF.md section 6, PR 46), so `no_copies` takes starts and waits
out together.

    python3 tools/kernel_pace.py --shape deepseek-v2-serve-agent64 \
        --block-sizes 16,32,64,128 --out chiprun_out/kernel_pace.json

`--shape`: a name of `SHAPES` (a cell's slots, heads, pool row, tables
and rings with their layers, and a seeded mix of cursor lengths).
`--block-sizes`: the same rows in pages of so many rows (the cell's
first).  `--check`: the whole kernel's result against plain `jax.numpy`
over random pools, before any timing.  `--rehearse`: the name's shape
cut to a toy and run in the Pallas interpreter on the CPU, to see that
the script runs: never a number.

It imports the kernel and is imported by nothing a cell runs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: slots, query heads, head width, pool row, value columns (0: a K
# pool and a V pool), page rows, context rows, ((table pages are
# context / page rows | ring rows, layers), ...), attention scale
SHAPES = {
    # one latent pool, 128 heads on a row of 512 + 64 stored 640 wide
    "deepseek-v2-serve-agent64": dict(
        slots=64, heads=128, d_head=0, row=640, d_value=512, bs=16,
        ctx=4096, layers=((4096, 5),), scale=0.1147),
    # grouped heads (32 over 4 K/V heads of 128): a table over 2 layers
    # and a ring of 1024 rows over 6
    "mellum2-12b-a2.5b-serve-agent96": dict(
        slots=96, heads=32, d_head=128, row=512, d_value=0, bs=16,
        ctx=4096, layers=((4096, 2), (1024, 6)), scale=0.0884),
    # plain multi-head attention, short cursors: a page or two a chunk
    "opt-1.3b-serve-closed32": dict(
        slots=32, heads=32, d_head=64, row=2048, d_value=0, bs=16,
        ctx=512, layers=((512, 24),), scale=0.125, mean_rows=140),
}
VARIANTS = ("whole", "no_copies", "no_products")


def lengths_of(shape):
    """The cursors' mix: lognormal about `mean_rows` (900: the long
    cells' mean by `sched_kv_pages_read_share`), cut to the context."""
    r = np.random.RandomState(0)
    mean = shape.get("mean_rows", 900)
    return np.clip(r.lognormal(np.log(mean), 0.6, shape["slots"]), 30,
                   int(shape["ctx"] * 0.83)).astype(np.int32)


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


@contextlib.contextmanager
def removed(variant):
    """The kernel is traced anew inside, without `variant`'s part, and
    anew after it (its call sits behind a module-level `jax.jit`, which
    would hand a later caller the trace it kept)."""
    import jax
    from jax.experimental.pallas import tpu as pltpu

    real_copy, real_switch = pltpu.make_async_copy, jax.lax.switch
    jax.clear_caches()
    if variant == "no_copies":
        pltpu.make_async_copy = lambda *a, **k: _NoCopy()
    elif variant == "no_products":
        jax.lax.switch = lambda index, branches, carry: carry
    elif variant != "whole":
        raise ValueError(f"no variant {variant!r}: one of {VARIANTS}")
    try:
        yield
    finally:
        pltpu.make_async_copy, jax.lax.switch = real_copy, real_switch
        jax.clear_caches()


def build(shape, bs, pa, interpret=False):
    """-> (a jitted function over every layer of every table and ring
    of `shape` at pages of `bs` rows, its arguments, what a reference
    needs beside them)."""
    import jax
    import jax.numpy as jnp

    s_n, h, row, d_value = (shape[k] for k in
                            ("slots", "heads", "row", "d_value"))
    kern, why = pa.select_paged_attention(
        d_model=h * (shape["d_head"] or 1), n_heads=h,
        d_head=shape["d_head"] or None, block_size=bs, kv_dtype="bf16",
        platform="tpu", interpret=interpret, kv_width=row,
        value_width=d_value or None)
    assert kern is not None, why
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def normal(dims, sigma):
        return (jax.random.normal(next(keys), dims, jnp.float32)
                * sigma).astype(jnp.bfloat16)

    lengths = lengths_of(shape)
    pools, tables, lens = [], [], []
    for rows, n_layers in shape["layers"]:
        nb = rows // bs
        dims = (n_layers, s_n * nb + 1, bs, row)
        pools.append(tuple(normal(dims, 0.5)
                           for _ in range(1 if d_value else 2)))
        tables.append(jnp.asarray(
            1 + np.arange(s_n * nb).reshape(s_n, nb), jnp.int32))
        lens.append(jnp.asarray(np.minimum(lengths, rows), jnp.int32))
    q = normal((s_n, h * (row if d_value else shape["d_head"])), 0.1)
    new = normal((s_n, row), 0.5)

    def f(q, pools):
        outs, back = [], []
        for pool, table, ln, (_, n_layers) in zip(
                pools, tables, lens, shape["layers"]):
            for layer in range(n_layers):
                if d_value:
                    out, *pool = kern(q, pool[0], None, table, ln, layer,
                                      shape["scale"],
                                      write=(new, None, ln - 1))
                else:
                    out, *pool = kern(q, pool[0], pool[1], table, ln,
                                      layer, shape["scale"],
                                      write=(new, new, ln - 1))
            outs.append(out)
            back.append(tuple(pool))
        return outs, back

    return (jax.jit(f, donate_argnums=(1,)), (q, pools),
            dict(tables=tables, lengths=lens, new=new))


def reference(shape, bs, q, pools, aux):
    """Each table's last layer through plain `jax.numpy`: the row
    written, the pages gathered, a softmax over the cursor's rows."""
    import jax
    import jax.numpy as jnp

    s_n, h, row, d_value = (shape[k] for k in
                            ("slots", "heads", "row", "d_value"))
    wants = []
    for pool, table, ln, (rows, n_layers) in zip(
            pools, aux["tables"], aux["lengths"], shape["layers"]):
        layer, lane = n_layers - 1, jnp.arange(s_n)
        at = ln - 1
        kv = [p[layer].at[table[lane, at // bs], at % bs].set(aux["new"])
              [table].reshape(s_n, rows, row).astype(jnp.float32)
              for p in pool]
        keys, values = kv[0], kv[-1][..., :d_value or row]
        if d_value:
            qh = q.reshape(s_n, h, row).astype(jnp.float32)
            sc = jnp.einsum("shw,stw->sht", qh, keys)
        else:
            dh = shape["d_head"]
            n_kv = row // dh
            qh = q.reshape(s_n, n_kv, h // n_kv, dh).astype(jnp.float32)
            sc = jnp.einsum("sgid,stgd->sgit", qh,
                            keys.reshape(s_n, rows, n_kv, dh)
                            ).reshape(s_n, h, rows)
        sc = jnp.where(jnp.arange(rows)[None, None] < ln[:, None, None],
                       sc * shape["scale"], -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        if d_value:
            want = jnp.einsum("sht,stv->shv", p, values)
        else:
            want = jnp.einsum(
                "sgit,stgd->sgid", p.reshape(s_n, n_kv, h // n_kv, rows),
                values.reshape(s_n, rows, n_kv, dh))
        wants.append(want.reshape(s_n, -1))
    return wants


def check(shape, bs, pa, interpret=False):
    """Largest difference of the whole kernel from `reference`, as a
    share of the reference's largest value (a bfloat16 pool: some
    1e-2)."""
    import jax

    f, (q, pools), aux = build(shape, bs, pa, interpret)
    wants = jax.jit(lambda q, pools: reference(shape, bs, q, pools, aux))(
        q, pools)
    wants = [np.asarray(w, np.float32) for w in wants]
    outs, _ = f(q, pools)
    return max(float(np.abs(np.asarray(out, np.float32) - want).max()
                     / np.abs(want).max())
               for out, want in zip(outs, wants))


def pace(shape, bs, pa, variant="whole", calls=30, interpret=False):
    """Milliseconds a call of every layer of every table and ring."""
    import jax

    with removed(variant):
        f, (q, pools), _ = build(shape, bs, pa, interpret)
        outs, pools = f(q, pools)
        jax.block_until_ready(outs)
        t = time.perf_counter()
        for _ in range(calls):
            outs, pools = f(q, pools)
        jax.block_until_ready(outs)
        took = time.perf_counter() - t
    return took / calls * 1e3


def toy(shape):
    """`shape` cut to what the interpreter walks in seconds."""
    return dict(shape, slots=3, heads=min(shape["heads"], 8),
                row=128 if shape["d_value"] else 2 * shape["d_head"],
                d_value=shape["d_value"] and 128, ctx=256,
                layers=tuple((min(rows, 256), 1)
                             for rows, _ in shape["layers"]),
                mean_rows=90)


def run(name, block_sizes=None, variants=VARIANTS, pa=None, calls=30,
        with_check=False, rehearse=False):
    """-> {"shape", "rows", "bs<n>.<variant>": ms, "bs<n>.pages",
    "bs<n>.tiling", "bs<n>.check"}."""
    import jax

    if pa is None:
        from paddle_tpu.kernels import paged_attention as pa
    shape = SHAPES[name]
    if rehearse:
        shape, calls = toy(shape), 1
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("kernel_pace: no TPU here; a time comes from a "
                         "chip run (--rehearse walks the script)")
    lengths = lengths_of(shape)
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse),
           "rows": int(sum(n * np.minimum(lengths, rows).sum()
                           for rows, n in shape["layers"]))}
    for bs in block_sizes or (shape["bs"],):
        res[f"bs{bs}.pages"] = int(sum(
            n * (-(-np.minimum(lengths, rows) // bs)).sum()
            for rows, n in shape["layers"]))
        for variant in variants:
            if rehearse and variant != "whole":
                continue    # the interpreter walks the whole kernel only
            res[f"bs{bs}.{variant}"] = round(
                pace(shape, bs, pa, variant, calls, rehearse), 4)
            print(f"{name} bs{bs}.{variant}", res[f"bs{bs}.{variant}"],
                  flush=True)
        if with_check:
            res[f"bs{bs}.check"] = check(shape, bs, pa, rehearse)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="deepseek-v2-serve-agent64",
                    choices=sorted(SHAPES))
    ap.add_argument("--block-sizes", default="")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    res = run(args.shape,
              [int(b) for b in args.block_sizes.split(",") if b],
              tuple(v for v in args.variants.split(",") if v),
              calls=args.calls, with_check=args.check,
              rehearse=args.rehearse)
    print(json.dumps(res))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(res, fh)
    return res


if __name__ == "__main__":
    main()
