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
first).  `--tables`: what a lane's table names, `consecutive` blocks
(a fresh pool's, a ring's: the runs the kernels' issue loop takes one
descriptor a group for) or the same blocks `shuffled` (no two in a
row: a descriptor a page); the paged shapes' alone.  `--check`: the
whole kernel's result against plain `jax.numpy`
over random pools, before any timing.  `--pages`: the paged shapes at
each of these static `pages` of `paged_attention` in one process, in
place of `tiling`'s (a chunk's pages in a tree before PR 66: how PR 66's
step 0 read 28 + 5 against 17 + 16 and 33 with no kernel line changed;
the chunks' CAP since, under which `chunk_cut` cuts a lane).  `--rows`:
every lane's cursor at so many rows in place of the shape's mix.
`--rehearse`: the name's shape cut to a toy and run in the Pallas
interpreter on the CPU, to see that the script runs: never a number;
beside it the VMEM the Mosaic call asks for at the REAL geometry
(`vmem_request`: a lowering, nothing runs).

The flash-attention kernels of a training step the same way
(`--shape opt-1.3b-train-seq2048`, and `flash-seq8192`,
`flash-seq8192-d128`, `flash-seq16384` for the shapes no cell runs: one
layer's forward call and its backward call, each alone, ms a call):
whole, `no_mask` (no tile applies the causal mask: WRONG results, true
time) and `uncut` (the backward's kernels take every live tile over all
its keys, as the forward does: right results, what
`flash_attention._causal_keys` saves), and the forward's two reductions
over a score tile's lanes each handing back the tile's first column:
`no_row_sum` (WRONG results; nothing to remove where the denominator
rides the `p . V` product: head size 64 since PR 58) and `no_row_max`
(right results short of an overflow).  `--check`: the kernel under
`jax.grad` against `flash_attention_reference`.  Copied into the
checkout of a commit before PR 47 the same command times that commit's
kernels (`--variants whole,no_mask`: they have no cut to undo).

A lightning indexer's score kernel the same way (`--shape
glm-5.2-serve-docqa64-indexer`: `kernels/paged_index_scores.py` over
the cell's two index planes at its lanes' cursors, ms for both calls):
whole, `no_copies`, `no_products`; `--check`: against the gather of the
whole table in plain `jax.numpy`, over the rows under the cursors.

A gated delta rule's kernel the same way (`--shape
solar-open2-250b-serve-docqa64-delta`: `kernels/delta_rule.py` over one
layer's states of the cell's 64 lanes, ms a call): whole, `no_copies`
(every grid step names the FIRST state block, which the pipeline then
fetches once and writes back once: the arithmetic alone, WRONG
results), `no_arithmetic` (a head's tile handed through: the copies
alone, WRONG results) and `xla`, `lm_block.delta_rule`'s `jax.numpy`
lines on the same arguments under the same donation (what the kernel
replaced).  `--heads-blocks`: the same call at so many heads a grid
step (the kernel's own choice first).  `--check`: against the
`jax.numpy` lines, state and o, as a share of their largest value
(float32 sums in another order: some 1e-6).

The routing of an expert layer the same way (`--shape <cell>-route`,
the ten cells with experts; rows, widths, k and groups from the cell's
own files): `lm_block.route` with `moe_ffn`'s dispatch and combine and
NO expert matmul between them (a stand-in hands the ordered rows
through), 32 layers chained in one call (the chip's host hands a call
over in some 0.2 ms: eight layers a call read 25 us a layer whatever
ran), ms a LAYER: whole (the choice `kernels/router_choice.py`'s call
where it is selected), `passes` (the choice `lm_block._largest`'s
fusions, what runs where the kernel is refused; right results),
`no_choice` (every choice of `route` a FIXED one of the same shapes,
spread as the router's own, and a maximum over the row in its place:
WRONG results) and `no_order` (`route`, then the identity order: the
rows repeated, the result reshaped; WRONG results).  `--check`: on the
same scores, the choice's experts, order and weights against the same
lines with `jax.lax.top_k`, and the order against the stable `argsort`,
bit for bit.  100 calls a reading; several shapes with commas between
run in one process.  Copied into the checkout of a commit before PR 63 the
same command times that commit's sorts.

A lightning indexer's SELECTION the same way (`--shape glm-5.2-select`,
`dots3-note-prev-select`: lanes, rows and k from the cell's own files,
cursors a document of the traffic file's and a seeded point of a
request's question and answer a lane): `kernels/select_rows.py` on 16
layers' scores chained in one call, ms a SELECTION: whole, `xla`
(`lm_block.select_rows` jitted alone on the same arguments: with
nothing else in the program the compiler keeps its loop's keys in VMEM,
the floor XLA can reach) and `no_counts` (the 32 passes removed: the
copies, the keys and the mask alone, WRONG results).  `--check`: the
kernel's mask, then the chip's jitted `select_rows`', against
`select_rows` called eagerly on the CPU backend, on the shape's scores
and on the same rounded to quarters (ties at the k-th score in every
lane, signed zeros among them): rows that differ, the kernel's 0 (the
jitted lines' not: under `jax.jit` XLA takes their `x + 0.0` for x and
-0.0 ranks under +0.0).  100 calls a reading.

It imports the kernel and is imported by nothing a cell runs.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
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
    # DeepSeek's latent row under 64 heads and a lightning indexer's
    # SELECTION: a row mask of 2048 rows a lane over docqa64's cursors
    # (a document and what a request has added: mean 4.9 k rows), a
    # table of 432 pages over 5 planes
    "glm-5.2-serve-docqa64": dict(
        slots=64, heads=64, d_head=0, row=640, d_value=512, bs=16,
        ctx=6912, layers=((6912, 5),), scale=0.0625, cursors="docqa",
        select=2048),
    # the same stored row under 64 heads on the 8 planes of 4 double
    # layers, and under 32 heads at 128 lanes on ONE plane (the latent
    # layer of six beside five delta-rule layers)
    "longcat-flash-serve-agent64": dict(
        slots=64, heads=64, d_head=0, row=640, d_value=512, bs=16,
        ctx=4096, layers=((4096, 8),), scale=0.0722),
    "ling-3.0-flash-serve-agent128": dict(
        slots=128, heads=32, d_head=0, row=640, d_value=512, bs=16,
        ctx=4096, layers=((4096, 1),), scale=0.0722),
    # dots3's two geometries: the sliding layers' latent RING (64 heads
    # on a row of 1024 + 64 stored 1152 wide, 33 pages a lane and every
    # lane's full, the window's 513 rows under a row mask) and the two
    # full layers' SELECTED table (128 heads on DeepSeek's row, 2048
    # rows selected under docqa64's cursors)
    "dots3-note-prev-serve-docqa64-ring": dict(
        slots=64, heads=64, d_head=0, row=1152, d_value=1024, bs=16,
        ctx=528, layers=((528, 3),), scale=0.0625, rows=528, select=513),
    "dots3-note-prev-serve-docqa64-table": dict(
        slots=64, heads=128, d_head=0, row=640, d_value=512, bs=16,
        ctx=6912, layers=((6912, 2),), scale=0.0722, cursors="docqa",
        select=2048),
    # the flash kernels of one layer's training step: 4 sequences of
    # 2048, 32 heads of 64 packed in pairs, bf16, causal, the blocks
    # `_select_blocks` gives (512 x 1024)
    "opt-1.3b-train-seq2048": dict(
        kernel="flash", batch=4, heads=32, d_head=64, seq=2048),
    # what no cell runs and `parallel/` and `chip_smoke.py` do: the same
    # tokens as ONE sequence (blocks of 1024 x 2048, the fused backward
    # at its VMEM request's edge), heads of 128 that fill the lanes
    # alone, and the length from which dq has a kernel of its own
    "flash-seq8192": dict(
        kernel="flash", batch=1, heads=32, d_head=64, seq=8192),
    "flash-seq8192-d128": dict(
        kernel="flash", batch=1, heads=16, d_head=128, seq=8192),
    "flash-seq16384": dict(
        kernel="flash", batch=1, heads=32, d_head=64, seq=16384),
    # a lightning indexer's scores: 32 index heads of 128 over ONE key
    # a row, two selecting layers' planes of 9216 blocks, a document of
    # 3072 to 6144 rows and 32 to 640 of question and answer under each
    # cursor (mean 4.9 k: docqa64's)
    "glm-5.2-serve-docqa64-indexer": dict(
        kernel="index", slots=64, heads=32, row=128, bs=16, ctx=6912,
        planes=2, blocks=9216),
    # one delta-rule layer's recurrence over the cell's lanes: 64 heads
    # of a [128 keys, 128 values] float32 matrix a lane, 268 MB
    "solar-open2-250b-serve-docqa64-delta": dict(
        kernel="delta", slots=64, heads=64, d_head=128),
}
# an expert layer's routing at a cell's rows and router (`run_route`)
ROUTE_CELLS = (
    "olmoe-1b-7b-serve-chat32", "mellum2-12b-a2.5b-serve-agent96",
    "granite-4.0-h-small-serve-chat64", "k-exaone-236b-a23b-serve-chat64",
    "deepseek-v2-serve-agent64", "longcat-flash-serve-agent64",
    "glm-5.2-serve-docqa64", "lfm2-24b-a2b-serve-agent128",
    "solar-open2-250b-serve-docqa64", "ling-3.0-flash-serve-agent128")
SHAPES.update({cell + "-route": dict(kernel="route", cell=cell)
               for cell in ROUTE_CELLS})
# a lightning indexer's selection at a cell's lanes, table and k
# (`run_select`)
SHAPES.update({
    "glm-5.2-select": dict(kernel="select", cell="glm-5.2-serve-docqa64"),
    "dots3-note-prev-select": dict(
        kernel="select", cell="dots3-note-prev-serve-docqa64")})
VARIANTS = ("whole", "no_copies", "no_products")
SELECT_VARIANTS = ("whole", "xla", "no_counts")
SELECT_LAYERS = 16
ROUTE_VARIANTS = ("whole", "passes", "no_choice", "no_order")
ROUTE_LAYERS = 32
DELTA_VARIANTS = ("whole", "no_copies", "no_arithmetic", "xla")
FLASH_VARIANTS = ("whole", "no_mask", "uncut", "no_row_sum", "no_row_max")
TABLES = ("consecutive", "shuffled")


def lengths_of(shape):
    """The cursors' mix: lognormal about `mean_rows` (900: the long
    cells' mean by `sched_kv_pages_read_share`), cut to the context;
    docqa64's where the shape asks for them; `rows` (a ring every lane
    has filled, `--rows`): every lane at so many."""
    if shape.get("rows"):
        return np.full(shape["slots"], shape["rows"], np.int32)
    if shape.get("cursors") == "docqa":
        return index_lengths(shape)
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


def lane_tables(s_n, nb, blocks, order):
    """[s_n, nb] int32 block ids of a pool of `blocks` blocks (block 0
    the null block): `consecutive`, a run of `nb` ascending ids a lane
    (lanes share blocks where the pool holds fewer than they name, as
    lanes that ask one document do); `shuffled`, the same ids in an
    order in which no run survives."""
    first = 1 + (np.arange(s_n) * nb) % max(1, blocks - nb)
    tables = first[:, None] + np.arange(nb)[None, :]
    if order == "shuffled":
        r = np.random.RandomState(2)
        tables = np.stack([r.permutation(row) for row in tables])
    elif order != "consecutive":
        raise ValueError(f"no tables {order!r}: one of {TABLES}")
    return tables.astype(np.int32)


def selected(shape, bs, pa, interpret=False, pages=0):
    """The kernel `select_paged_attention` gives at `shape`'s geometry
    in pages of `bs` rows; `pages`: the same call at that static
    `pages` of `paged_attention` (a chunk's pages in a tree before
    PR 66, the chunks' cap since) in place of `tiling`'s."""
    h, row, d_value = (shape[k] for k in ("heads", "row", "d_value"))
    kern, why = pa.select_paged_attention(
        d_model=h * (shape["d_head"] or 1), n_heads=h,
        d_head=shape["d_head"] or None, block_size=bs, kv_dtype="bf16",
        platform="tpu", interpret=interpret, kv_width=row,
        value_width=d_value or None)
    assert kern is not None, why
    if not pages:
        return kern

    def attend(q, pool_k, pool_v, tables, lengths, layer, scale,
               write=None, select=None):
        return pa.paged_attention(
            q, pool_k, pool_v, tables, lengths, layer, scale=float(scale),
            pages=pages, tile=min(kern.tiling(tables.shape[1])[1], pages),
            n_heads=h, d_head=shape["d_head"] or 1, d_value=d_value,
            interpret=interpret, write=write, select=select)

    attend.tiling = lambda table_pages: (
        pages, min(kern.tiling(table_pages)[1], pages))
    return attend


def build(shape, bs, pa, interpret=False, order="consecutive", pages=0):
    """-> (a jitted function over every layer of every table and ring
    of `shape` at pages of `bs` rows (`pages`: `selected`'s), its
    arguments, what a reference needs beside them)."""
    import jax
    import jax.numpy as jnp

    s_n, h, row, d_value = (shape[k] for k in
                            ("slots", "heads", "row", "d_value"))
    kern = selected(shape, bs, pa, interpret, pages)
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
            lane_tables(s_n, nb, s_n * nb + 1, order)))
        lens.append(jnp.asarray(np.minimum(lengths, rows), jnp.int32))
    q = normal((s_n, h * (row if d_value else shape["d_head"])), 0.1)
    new = normal((s_n, row), 0.5)
    select = None
    if shape.get("select"):
        # the rows an indexer chose: so many of those under the cursor,
        # the newest among them (the row the call writes)
        r = np.random.RandomState(3)
        select = np.zeros((s_n, shape["layers"][0][0]), bool)
        for lane, n in enumerate(np.asarray(lens[0])):
            select[lane, r.permutation(n)[:shape["select"]]] = True
            select[lane, n - 1] = True
        select = jnp.asarray(select)

    def f(q, pools):
        outs, back = [], []
        for pool, table, ln, (_, n_layers) in zip(
                pools, tables, lens, shape["layers"]):
            for layer in range(n_layers):
                if d_value:
                    out, *pool = kern(q, pool[0], None, table, ln, layer,
                                      shape["scale"],
                                      write=(new, None, ln - 1),
                                      select=select)
                else:
                    out, *pool = kern(q, pool[0], pool[1], table, ln,
                                      layer, shape["scale"],
                                      write=(new, new, ln - 1))
            outs.append(out)
            back.append(tuple(pool))
        return outs, back

    return (jax.jit(f, donate_argnums=(1,)), (q, pools),
            dict(tables=tables, lengths=lens, new=new, select=select))


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
        seen = jnp.arange(rows)[None] < ln[:, None]
        if aux["select"] is not None:
            seen &= aux["select"]
        sc = jnp.where(seen[:, None], sc * shape["scale"], -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        if d_value:
            want = jnp.einsum("sht,stv->shv", p, values)
        else:
            want = jnp.einsum(
                "sgit,stgd->sgid", p.reshape(s_n, n_kv, h // n_kv, rows),
                values.reshape(s_n, rows, n_kv, dh))
        wants.append(want.reshape(s_n, -1))
    return wants


def check(shape, bs, pa, interpret=False, order="consecutive", pages=0):
    """Largest difference of the whole kernel from `reference`, as a
    share of the reference's largest value (a bfloat16 pool: some
    1e-2)."""
    import jax

    f, (q, pools), aux = build(shape, bs, pa, interpret, order, pages)
    wants = jax.jit(lambda q, pools: reference(shape, bs, q, pools, aux))(
        q, pools)
    wants = [np.asarray(w, np.float32) for w in wants]
    outs, _ = f(q, pools)
    return max(float(np.abs(np.asarray(out, np.float32) - want).max()
                     / np.abs(want).max())
               for out, want in zip(outs, wants))


def pace(shape, bs, pa, variant="whole", calls=30, interpret=False,
         order="consecutive", pages=0):
    """Milliseconds a call of every layer of every table and ring."""
    import jax

    with removed(variant):
        f, (q, pools), _ = build(shape, bs, pa, interpret, order, pages)
        outs, pools = f(q, pools)
        jax.block_until_ready(outs)
        t = time.perf_counter()
        for _ in range(calls):
            outs, pools = f(q, pools)
        jax.block_until_ready(outs)
        took = time.perf_counter() - t
    return took / calls * 1e3


def vmem_request(shape, bs, pa, pages=0):
    """Bytes of VMEM the Mosaic call of each table and ring of `shape`
    asks for at its REAL geometry, lowered for a TPU from shapes alone
    (nothing runs, no chip is needed): the scratch operands once, a
    grid step's blocks twice (the pipeline's two buffers).  The
    compiler's own verdict on a v5e's 16 MiB is
    tests/test_kernels_lower_tpu.py's."""
    import base64
    import re

    import jax
    import jax.numpy as jnp
    from jax._src.lib.mlir import ir

    s_n, h, row, d_value = (shape[k] for k in
                            ("slots", "heads", "row", "d_value"))
    kern = selected(shape, bs, pa, pages=pages)
    sizes = {"bf16": 2, "f32": 4, "i32": 4}
    requests = []
    for rows, n_layers in shape["layers"]:
        nb = rows // bs
        pool = jax.ShapeDtypeStruct((n_layers, s_n * nb + 1, bs, row),
                                    jnp.bfloat16)
        new = jax.ShapeDtypeStruct((s_n, row), jnp.bfloat16)
        lengths = jax.ShapeDtypeStruct((s_n,), jnp.int32)

        def f(q, pool, new, tables, lengths, select):
            return kern(q, pool, None if d_value else pool, tables, lengths,
                        0, shape["scale"],
                        write=(new, None if d_value else new, lengths - 1),
                        **({"select": select} if d_value
                           and shape.get("select") else {}))

        text = jax.jit(f).trace(
            jax.ShapeDtypeStruct(
                (s_n, h * (row if d_value else shape["d_head"])),
                jnp.bfloat16), pool, new,
            jax.ShapeDtypeStruct((s_n, nb), jnp.int32), lengths,
            jax.ShapeDtypeStruct((s_n, rows), jnp.bool_)).lower(
                lowering_platforms=("tpu",)).as_text()
        (config,) = re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"', text)
        body = json.loads(config.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(
                base64.b64decode(body)).operation.get_asm(
                    enable_debug_info=False)
        (main,) = [line for line in module.splitlines()
                   if 'sym_name = "main"' in line]
        types = re.findall(r"memref<[^>]*>>", main[
            main.index("function_type = ("):main.index(") -> ()")])
        n_scratch = int(re.search(r"scratch_operands = (\d+)", main).group(1))
        operands = [
            int(np.prod([int(d) for d in dims.split("x") if d])) * sizes[dt]
            if space == "vmem" else 0
            for dims, dt, space in (re.match(
                r"memref<((?:\d+x)*)([^,]+), #tpu.memory_space<(\w+)>>",
                t).groups() for t in types)]
        scratch = sum(operands[len(operands) - n_scratch:])
        requests.append(2 * sum(operands) - scratch)
    return requests


# ---------------------------------------------------------------------------
# the flash-attention kernels of a training step
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def flash_removed(variant, fa):
    """`fa`'s kernels traced anew inside without `variant`'s part: the
    select that puts NEG_INF above the diagonal handing its scores
    through (`no_mask`), the table of a tile's cases with the one case
    of a kernel that takes a live tile whole (`uncut`), or a reduction
    over a [rows, keys] tile's lanes handing back the tile's first
    column (`no_row_sum`, `no_row_max`: the forward's alone reduce so)."""
    import jax
    import jax.numpy as jnp

    real_where, real_keys = jnp.where, getattr(fa, "_causal_keys", None)
    reductions = {"no_row_sum": "sum", "no_row_max": "max"}
    real_reduce = getattr(jnp, reductions.get(variant, "sum"))

    def first_column(x, axis=None, keepdims=False, **kw):
        if axis == 1 and keepdims and x.ndim == 2:
            return x[:, :1]
        return real_reduce(x, axis=axis, keepdims=keepdims, **kw)

    jax.clear_caches()
    if variant == "no_mask":
        jnp.where = lambda keep, x, y: (
            x if isinstance(y, float) and y == fa.NEG_INF
            else real_where(keep, x, y))
    elif variant == "uncut":
        if real_keys is None:
            raise SystemExit("kernel_pace: these kernels cut nothing")
        fa._causal_keys = lambda bq, bk: [(1 - bq, None, bk)]
    elif variant in reductions:
        setattr(jnp, reductions[variant], first_column)
    elif variant != "whole":
        raise ValueError(
            f"no variant {variant!r}: one of {FLASH_VARIANTS}")
    try:
        yield
    finally:
        jnp.where = real_where
        if variant in reductions:
            setattr(jnp, reductions[variant], real_reduce)
        if real_keys is not None:
            fa._causal_keys = real_keys
        jax.clear_caches()


def build_flash(shape, fa, interpret=False):
    """-> (the jitted forward call, the jitted backward call, their
    arguments in the kernels' layout, the same as [b, s, h, d])."""
    import jax
    import jax.numpy as jnp

    dims = tuple(shape[k] for k in ("batch", "seq", "heads", "d_head"))
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v, do = ((jax.random.normal(key, dims, jnp.float32) * 0.5
                    ).astype(jnp.bfloat16) for key in keys)
    plan = fa._plan(q, k, v, True, None, shape.get("block_q"),
                    shape.get("block_k"), interpret, 0, "tpu")
    assert plan is not None, "the kernel is not selected at this shape"
    kq, kk, kv, kdo = (fa._to_kernel(x, plan) for x in (q, k, v, do))
    fwd = jax.jit(lambda q, k, v: fa._fwd_pallas(q, k, v, *plan))
    bwd = jax.jit(lambda q, k, v, lse, delta, do: fa._bwd_pallas(
        q, k, v, lse, delta, do, *plan))
    return fwd, bwd, (kq, kk, kv, kdo), (q, k, v, do), plan


def timed(f, args, calls):
    """Milliseconds a call of `f(*args)`, after one call that compiles
    it."""
    import jax

    jax.block_until_ready(f(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / calls * 1e3


def pace_flash(shape, fa, variant="whole", calls=30, interpret=False):
    """(ms a forward call, ms a backward call), each kernel alone."""
    import jax.numpy as jnp

    with flash_removed(variant, fa):
        fwd, bwd, (q, k, v, do), _, _ = build_flash(shape, fa, interpret)
        _, lse = fwd(q, k, v)
        # the kernel's time does not follow its values: zeros for
        # `delta`, the rowsum(do * o) `_backward` hands it beside lse
        return (timed(fwd, (q, k, v), calls),
                timed(bwd, (q, k, v, lse, jnp.zeros_like(lse), do), calls))


def check_flash(shape, fa, interpret=False):
    """Largest difference of the kernel's result and of its three
    gradients from `flash_attention_reference`'s, each as a share of the
    reference's largest value (bfloat16 operands: some 1e-2)."""
    import jax
    import jax.numpy as jnp

    _, _, _, (q, k, v, do), plan = build_flash(shape, fa, interpret)

    def run(f):
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(do)

    got = run(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, block_q=plan.block_q, block_k=plan.block_k,
        interpret=interpret, min_seq_k=0, platform="tpu"))
    want = run(lambda q, k, v: fa.flash_attention_reference(
        q, k, v, causal=True))
    return max(float(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)
                             ).max() / jnp.abs(w.astype(jnp.float32)).max())
               for g, w in zip(got, want))


def run_flash(name, variants=FLASH_VARIANTS, calls=30, with_check=False,
              rehearse=False):
    """-> {"shape", "blocks", "fwd.<variant>": ms, "bwd.<variant>": ms,
    "subtiles", "check"}."""
    import jax

    # the package's attribute of that name is the function
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    shape = SHAPES[name]
    if rehearse:
        # blocks of 128 x 256 over 512: the cell's 4 x 2 tiles a head
        shape, calls = dict(shape, batch=1, heads=2, seq=512, block_q=128,
                            block_k=256), 1
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse)}
    plan = build_flash(shape, fa, rehearse)[-1]
    res["blocks"] = [plan.block_q, plan.block_k]
    if hasattr(fa, "causal_subtiles"):
        # a head's grid: the forward's, the backward's, the live ones
        res["subtiles"] = list(fa.causal_subtiles(
            shape["seq"], shape["seq"], plan.block_q, plan.block_k))
    for variant in variants:
        if rehearse and variant != "whole":
            continue    # the interpreter walks the whole kernel only
        f, b = pace_flash(shape, fa, variant, calls, rehearse)
        res[f"fwd.{variant}"], res[f"bwd.{variant}"] = (round(f, 4),
                                                        round(b, 4))
        print(f"{name} {variant} fwd {f:.4f} bwd {b:.4f}", flush=True)
    if with_check:
        # the reference holds a head's whole score matrix in float32,
        # and its gradients': a head pair of a long sequence is what
        # fits beside them
        few = dict(shape, heads=min(shape["heads"], 2))
        res["check"] = check_flash(
            few if shape["seq"] > 4096 else shape, fa, rehearse)
    return res


# ---------------------------------------------------------------------------
# a lightning indexer's scores over its index-key planes
# ---------------------------------------------------------------------------

def index_lengths(shape):
    """docqa64's cursors: a document and what a request has added to
    it so far, uniform both, cut to the context."""
    r = np.random.RandomState(0)
    ctx, s_n = shape["ctx"], shape["slots"]
    docs = r.randint(ctx * 4 // 9, ctx * 8 // 9 + 1, s_n)
    return np.minimum(docs + r.randint(ctx // 216, ctx * 5 // 54, s_n),
                      ctx).astype(np.int32)


def build_index(shape, pis, interpret=False, order="consecutive"):
    """-> (a jitted function that scores every plane of `shape`
    through the selected kernel, its arguments, the lanes' lengths)."""
    import jax
    import jax.numpy as jnp

    s_n, h, d, bs = (shape[k] for k in ("slots", "heads", "row", "bs"))
    kern, why = pis.select_index_scores(
        index_head_dim=d, block_size=bs, kv_dtype="bf16", platform="tpu",
        interpret=interpret)
    assert kern is not None, why
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    nb = shape["ctx"] // bs
    pool = (jax.random.normal(keys[0], (shape["planes"], shape["blocks"],
                                        bs, d), jnp.float32)
            ).astype(jnp.bfloat16)
    q = jax.random.normal(keys[1], (s_n, h, d), jnp.float32) * 0.3
    w = jax.random.normal(keys[2], (s_n, h), jnp.float32)
    tables = jnp.asarray(lane_tables(s_n, nb, shape["blocks"], order))
    lengths = jnp.asarray(index_lengths(shape))

    def f(q, w, pool):
        return [kern(q, w, pool, tables, lengths, plane)
                for plane in range(shape["planes"])]

    return jax.jit(f), (q, w, pool), dict(
        tables=tables, lengths=lengths, tiling=kern.tiling(nb))


def check_index(shape, pis, interpret=False, order="consecutive"):
    """Largest difference, over the rows under the cursors, of the
    kernel's scores from the gather of the whole table's, as a share of
    the largest score (float32 sums in another order: some 1e-6)."""
    import jax
    import jax.numpy as jnp

    f, (q, w, pool), aux = build_index(shape, pis, interpret, order)
    s_n, rows = q.shape[0], shape["ctx"]
    valid = np.arange(rows)[None, :] < np.asarray(aux["lengths"])[:, None]
    worst = 0.0
    for plane, got in enumerate(f(q, w, pool)):
        keys = pool[plane, aux["tables"]].reshape(s_n, rows, -1)
        dots = jax.lax.dot_general(
            q.astype(keys.dtype), keys, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        want = np.asarray((jax.nn.relu(dots) * w[:, :, None]).sum(axis=1))
        worst = max(worst, float(
            np.abs(np.where(valid, np.asarray(got) - want, 0.0)).max()
            / np.abs(want).max()))
    return worst


def run_index(name, variants=VARIANTS, calls=30, with_check=False,
              rehearse=False, order="consecutive"):
    """-> {"shape", "rows", "pages", "tiling", "<variant>": ms for the
    planes' calls together, "check"}."""
    import jax

    from paddle_tpu.kernels import paged_index_scores as pis

    shape = SHAPES[name]
    if rehearse:
        shape, calls = dict(shape, slots=3, heads=4, ctx=512, blocks=97), 1
    lengths = index_lengths(shape)
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse), "tables": order,
           "rows": int(shape["planes"] * lengths.sum()),
           "pages": int(shape["planes"]
                        * (-(-lengths // shape["bs"])).sum())}
    for variant in variants:
        if rehearse and variant != "whole":
            continue    # the interpreter walks the whole kernel only
        with removed(variant):
            f, args, aux = build_index(shape, pis, rehearse, order)
            res["tiling"] = list(aux["tiling"])
            res[variant] = round(timed(f, args, calls), 4)
        print(f"{name} {variant}", res[variant], flush=True)
    if with_check:
        res["check"] = check_index(shape, pis, rehearse, order)
    return res


# ---------------------------------------------------------------------------
# a gated delta rule's recurrence over the lanes' matrix states
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def delta_removed(variant, dr):
    """`dr`'s kernel traced anew inside without `variant`'s part: every
    grid step on the first state block (`no_copies`), or a head's rule
    handing its tile through (`no_arithmetic`)."""
    import jax
    import jax.numpy as jnp

    real_block, real_head = dr._state_block, dr._head
    jax.clear_caches()
    if variant == "no_copies":
        dr._state_block = lambda lane, blk, fresh, live: (0, 0, 0, 0)
    elif variant == "no_arithmetic":
        dr._head = lambda s, e, k, q, v, beta: (
            jnp.zeros((k.shape[0], v.shape[1]), v.dtype) if s is None
            else s, v)
    elif variant not in ("whole", "xla"):
        raise ValueError(
            f"no variant {variant!r}: one of {DELTA_VARIANTS}")
    try:
        yield
    finally:
        dr._state_block, dr._head = real_block, real_head
        jax.clear_caches()


def build_delta(shape, dr, variant="whole", heads_block=None,
                interpret=False):
    """-> (the jitted recurrence, the state donated: the kernel's, or
    `lm_block.delta_rule`'s lines under `xla`; its arguments, the state
    first; the heads a grid step takes)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lm_block

    s_n, h_n, k_n = (shape[k] for k in ("slots", "heads", "d_head"))
    keys = jax.random.split(jax.random.PRNGKey(1), 6)

    def unit(x):
        return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    state = jax.random.normal(keys[0], (s_n, h_n, k_n, k_n), jnp.float32)
    q, k, v = (jax.random.normal(key, (s_n, h_n, k_n), jnp.float32)
               for key in keys[1:4])
    q, k = unit(q) * k_n ** -0.5, unit(k)
    g = -jax.nn.softplus(jax.random.normal(keys[4], (s_n, h_n, k_n)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[5], (s_n, h_n)))
    fresh = jnp.zeros(s_n, bool).at[0].set(True)
    live = jnp.ones(s_n, bool).at[-1].set(False)
    rule, block = lm_block.delta_rule, None
    if variant != "xla":
        chooses = dr._heads_block
        if heads_block:
            dr._heads_block = lambda heads, d_head: heads_block
        try:
            kern, why = dr.select_delta_rule(
                lanes=s_n, heads=h_n, d_head=k_n, platform="tpu",
                interpret=interpret)
        finally:
            dr._heads_block = chooses
        assert kern is not None, why
        rule, block = kern.rule, kern.heads_block
    return (jax.jit(rule, donate_argnums=(0,)),
            (state, q, k, v, g, beta, fresh, live), block)


def check_delta(shape, dr, heads_block=None, interpret=False):
    """-> (the heads a grid step takes, the largest difference of the
    kernel's state and o from the `jax.numpy` lines', each as a share
    of the lines' largest value)."""
    import jax

    ref, args, _ = build_delta(shape, dr, "xla")
    want = [np.asarray(x) for x in ref(*args)]      # donates its state
    f, args, block = build_delta(shape, dr, "whole", heads_block,
                                 interpret)
    got = jax.block_until_ready(f(*args))
    return block, max(
        float(np.abs(np.asarray(g) - w).max() / np.abs(w).max())
        for g, w in zip(got, want))


def run_delta(name, variants=DELTA_VARIANTS, heads_blocks=(), calls=30,
              with_check=False, rehearse=False):
    """-> {"shape", "state_bytes", "hb<n>.<variant>": ms a call,
    "xla": ms a call, "hb<n>.check"}."""
    import jax

    from paddle_tpu.kernels import delta_rule as dr

    shape = SHAPES[name]
    if rehearse:
        shape, calls = dict(shape, slots=3, heads=4), 1
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse),
           "state_bytes": 4 * shape["slots"] * shape["heads"]
           * shape["d_head"] ** 2}
    for hb in heads_blocks or (None,):
        for variant in variants:
            if rehearse and variant not in ("whole", "xla"):
                continue    # the interpreter walks the whole kernel only
            if variant == "xla" and "xla" in res:
                continue
            with delta_removed(variant, dr):
                f, (state, *rest), block = build_delta(
                    shape, dr, variant, hb, rehearse)
                state, o = f(state, *rest)
                jax.block_until_ready(o)
                t = time.perf_counter()
                for _ in range(calls):
                    state, o = f(state, *rest)
                jax.block_until_ready(o)
                ms = (time.perf_counter() - t) / calls * 1e3
            key = "xla" if variant == "xla" else f"hb{block}.{variant}"
            res[key] = round(ms, 4)
            print(f"{name} {key}", res[key], flush=True)
        if with_check:
            block, res_check = check_delta(shape, dr, hb, rehearse)
            res[f"hb{block}.check"] = res_check
    return res


def cell_files(cell):
    """(configuration, traffic mix) of a cell, as its job reads them."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def load(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    w = next(w for w in load("BENCHMARK.json")["workloads"]
             if w["name"] == cell)
    return (load("perf", "configs", w["config"] + ".json"),
            load("perf", "traffic", w["traffic"] + ".json"))


def route_cell(cell):
    """(block description, rows of a tick, the model's width) of a
    serving cell, from its own files as its job reads them."""
    from paddle_tpu.models import lm_block

    m, t = cell_files(cell)
    b = m["block"]
    spec = lm_block.BlockSpec(**dict(
        b["spec"], **{f: m[k] for f, k in b["from_keys"].items()}))
    return spec, int(t["slots"]), int(m["hidden_size"])


def fixed_choice(spec, rows):
    """A choice [rows, k] int32 of distinct experts that no score
    decides, spread as a router with random weights spreads them:
    `topk_group` of `n_group` groups a row, then k of their experts."""
    rng = np.random.RandomState(193)
    width = spec.n_experts + spec.zero_experts
    out = np.empty((rows, spec.experts_per_token), np.int32)
    for r in range(rows):
        pool = np.arange(width)
        if spec.n_group > 1:
            kept = rng.choice(spec.n_group, spec.topk_group, replace=False)
            pool = pool.reshape(spec.n_group, -1)[kept].reshape(-1)
        out[r] = rng.choice(pool, spec.experts_per_token, replace=False)
    return out


@contextlib.contextmanager
def route_removed(variant, spec, lm_block):
    """While it lasts every choice of `lm_block.route` (its `_largest`,
    and `jax.lax.top_k` for a tree that still sorts) is `fixed_choice`
    or the first k, with a maximum over the row so that what feeds the
    choice stays live; `whole` and `no_order` patch nothing."""
    import jax
    import jax.numpy as jnp

    if variant != "no_choice":
        yield
        return
    width = spec.n_experts + spec.zero_experts

    def fixed(x, k):
        top = x.max(-1, keepdims=True)
        if x.shape[-1] == width and k == spec.experts_per_token:
            at = jnp.asarray(fixed_choice(spec, x.shape[0]))
        else:
            at = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32),
                                  x.shape[:-1] + (k,))
        # no score is this: a zero the compiler cannot fold
        return (jnp.broadcast_to(top, x.shape[:-1] + (k,)),
                at + (top == 12345.678).astype(jnp.int32))

    saved = jax.lax.top_k, getattr(lm_block, "_largest", None)
    jax.lax.top_k = fixed
    if saved[1] is not None:
        lm_block._largest = fixed
    try:
        yield
    finally:
        jax.lax.top_k = saved[0]
        if saved[1] is not None:
            lm_block._largest = saved[1]


class _HandThrough:
    """The experts' stand-in: the ordered rows come back as the experts'
    result, the plan kept live."""
    name = "hand_through"

    @staticmethod
    def plan(sizes):
        return sizes, sizes

    @staticmethod
    def gate_up(rows, w_gate, w_up, plan):
        return rows + (plan[0][0] + plan[1][-1]).astype(rows.dtype)

    @staticmethod
    def down(act, w_down, plan):
        import jax.numpy as jnp

        return act.astype(jnp.float32)


def choice_kernel(spec, rows, interpret=False):
    """{"choice": the tree's choice kernel for a TPU at these shapes, or
    None where it is refused}; {} in a tree before PR 63, whose `route`
    takes none."""
    from paddle_tpu.models import lm_block

    if not hasattr(lm_block, "_largest"):
        return {}
    from paddle_tpu.kernels import router_choice

    return {"choice": router_choice.select_router_choice(
        rows=rows, width=spec.n_experts + spec.zero_experts,
        k=spec.experts_per_token, n_group=spec.n_group,
        topk_group=spec.topk_group, group_score=spec.group_score,
        platform="tpu", interpret=interpret)[0]}


def route_inputs(shape, rehearse=False):
    """-> (block description, tokens m [rows, d] float32, the router's
    matrix [d, width] float32, its choice bias [width] or None) at the
    cell's shape, seeded."""
    import jax
    import jax.numpy as jnp

    spec, t_n, d = route_cell(shape["cell"])
    if rehearse:
        t_n, d = 4, 32
    width = spec.n_experts + spec.zero_experts
    keys = jax.random.split(jax.random.key(63), 3)
    m = jax.random.normal(keys[0], (t_n, d), jnp.float32)
    w_router = jax.random.normal(keys[1], (d, width), jnp.float32) * d ** -0.5
    b_router = (0.01 * jax.random.normal(keys[2], (width,), jnp.float32)
                if spec.router_bias else None)
    return spec, m, w_router, b_router


def build_route(shape, variant="whole", rehearse=False):
    """-> (the compiled chain of `ROUTE_LAYERS` routed layers, its
    arguments)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lm_block

    spec, m, w_router, b_router = route_inputs(shape, rehearse)
    t_n, k_n = m.shape[0], spec.experts_per_token
    first, e_n = spec.held
    stub = jnp.zeros((1,), jnp.bfloat16)
    # whole and no_order: with the tree's choice kernel at these shapes
    chosen = ({} if variant in ("passes", "no_choice")
              else choice_kernel(spec, t_n, rehearse))

    def layer(m):
        if variant != "no_order":
            return lm_block.moe_ffn(
                spec, m, w_router, stub, stub, stub, experts=_HandThrough,
                b_router=b_router, **chosen)[0]
        top_w, top_e = lm_block.route(spec, m, w_router, b_router, **chosen)
        out = jnp.repeat(m.astype(jnp.bfloat16), k_n, axis=0).astype(
            jnp.float32).reshape(t_n, k_n, -1)
        if spec.has_unheld:
            here = (top_e >= first) & (top_e < first + e_n)
            out = jnp.where(here[..., None], out, 0.0)
        return (out * top_w[..., None]).sum(axis=1)

    def chain(m):
        for _ in range(ROUTE_LAYERS):
            m = m + layer(m)
        return m

    with route_removed(variant, spec, lm_block):
        return jax.jit(chain).lower(m).compile(), (m,)


def check_route(shape, rehearse=False):
    """-> whether, on the SAME scores (the router's, made once and
    kept), `lm_block._chosen`'s weights and experts as the tree makes
    them (its kernel where one is selected) are bit for bit those of
    the same lines with `jax.lax.top_k`, and `_by_expert`'s order,
    places and sizes the stable `argsort`'s, at the cell's shape.  (A
    whole `route` against a whole `route` differs in the scores' last
    bits on the chip whatever chooses: beside another consumer the
    compiler turns the router's product over, `fb_oi->bf` for
    `bf_io->bf`, and its six bfloat16 passes add up in another order.)
    A tree that still sorts is its own reference."""
    import functools

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lm_block

    if not hasattr(lm_block, "_chosen"):
        return True
    spec, m, w_router, b_router = route_inputs(shape, rehearse)
    logits = jnp.dot(m, w_router, precision=jax.lax.Precision.HIGHEST)
    probs = (jax.nn.sigmoid(logits) if spec.router == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))

    def chosen(**choice):
        return jax.jit(functools.partial(lm_block._chosen, spec, **choice))(
            probs, b_router)

    got = chosen(**choice_kernel(spec, m.shape[0], rehearse))
    passes, lm_block._largest = lm_block._largest, jax.lax.top_k
    try:
        want = chosen()
    finally:
        lm_block._largest = passes
    first, e_n = spec.held
    flat_e = got[1].reshape(-1) - first
    flat_e = jnp.where((flat_e >= 0) & (flat_e < e_n), flat_e, e_n)
    by_sort = jnp.argsort(flat_e, stable=True)
    return all(
        np.array_equal(np.asarray(g), np.asarray(w)) for g, w in zip(
            got + tuple(jax.jit(functools.partial(
                lm_block._by_expert, e_n=e_n))(flat_e)),
            want + (by_sort, jnp.zeros_like(by_sort).at[by_sort].set(
                jnp.arange(len(by_sort))),
                jnp.zeros(e_n, jnp.int32).at[flat_e].add(1, mode="drop"))))


def run_route(name, variants=ROUTE_VARIANTS, calls=100, with_check=False,
              rehearse=False):
    """-> {"shape", "rows", "width", "k", "groups", "<variant>": ms a
    layer, "sorts": `sort` ops a layer in the whole chain's compiled
    text, "check"}."""
    import jax

    shape = SHAPES[name]
    spec, t_n, d = route_cell(shape["cell"])
    chosen = choice_kernel(spec, t_n, rehearse)
    kernel = chosen.get("choice")
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse), "rows": t_n, "d_model": d,
           "choice": (kernel.name if kernel is not None
                      else "passes" if chosen else "top_k"),
           "width": spec.n_experts + spec.zero_experts,
           "k": spec.experts_per_token,
           "groups": [spec.n_group, spec.topk_group, spec.group_score],
           "held": list(spec.held)}
    for variant in variants:
        if variant == "passes" and kernel is None and "whole" in res:
            res[variant] = res["whole"]     # the same program
            continue
        f, args = build_route(shape, variant, rehearse)
        if variant == "whole":
            res["sorts"] = f.as_text().count(" sort(") / ROUTE_LAYERS
        res[variant] = round(
            timed(f, args, 1 if rehearse else calls) / ROUTE_LAYERS, 5)
        print(f"{name} {variant}", res[variant], flush=True)
    if with_check:
        res["check"] = check_route(shape, rehearse)
    return res


# ---------------------------------------------------------------------------
# a lightning indexer's selection over a tick's index scores
# ---------------------------------------------------------------------------

def select_cell(cell):
    """(lanes, rows of a lane's table, k, the lanes' cursors) of a
    selecting cell, from its own files: lane c at document c mod 16 of
    the traffic file's and a seeded point of pair c's question and
    answer."""
    spec, lanes, _ = route_cell(cell)
    _, t = cell_files(cell)
    docs = np.asarray(t["documents"]["lengths"])
    pairs = np.asarray(t["lengths"]["table"]).sum(axis=1)
    at = np.arange(lanes)
    cursors = docs[at % len(docs)] + (
        np.random.RandomState(0).rand(lanes)
        * pairs[at % len(pairs)]).astype(np.int64)
    rows = int(t["context"])
    return lanes, rows, int(spec.index_topk), np.minimum(
        cursors, rows - 1).astype(np.int32)


def select_geometry(shape, rehearse=False):
    if rehearse:
        return 3, 256, 40, np.array([255, 17, 0], np.int32)
    return select_cell(shape["cell"])


def build_select(shape, sr, variant="whole", rehearse=False):
    """-> (a jitted function that selects on `SELECT_LAYERS` layers'
    scores in one call: the kernel's, or under `xla`
    `lm_block.select_rows`'s lines; its arguments)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lm_block

    lanes, rows, k, cursors = select_geometry(shape, rehearse)
    layers = 2 if rehearse else SELECT_LAYERS
    scores = jax.random.normal(jax.random.PRNGKey(1), (layers, lanes, rows),
                               jnp.float32)
    if variant == "xla":
        def select(s, cur):
            return lm_block.select_rows(
                s, jnp.arange(rows)[None, :] <= cur[:, None], k)
    else:
        kern, why = sr.select_index_selection(
            rows=rows, lanes=lanes, k=k, platform="tpu", interpret=rehearse)
        assert kern is not None, why
        select = kern.select

    def f(scores, cursors):
        return jnp.stack([select(scores[i], cursors)
                          for i in range(layers)])

    return jax.jit(f), (scores, jnp.asarray(cursors))


def check_select(shape, sr, rehearse=False):
    """Rows of the kernel's masks that differ from `select_rows`' called
    EAGERLY on the CPU backend (the definition: under `jax.jit` XLA
    takes `x + 0.0` for x on every backend, and the jitted lines then
    rank -0.0 UNDER +0.0 where the words, the eager call, the plain
    references' `scores == kth` and the kernel tie them), then rows of
    the chip's jitted `select_rows` that differ from it: each on the
    shape's scores, and on the same rounded to quarters with signed
    zeros and infinities among them (ties at the k-th score)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import lm_block

    f, (scores, cursors) = build_select(shape, sr, "whole", rehearse)
    lines, _ = build_select(shape, sr, "xla", rehearse)
    _, rows, k, cur = select_geometry(shape, rehearse)
    valid = np.arange(rows)[None, :] <= cur[:, None]
    tied = np.round(np.asarray(scores) * 4) / 4
    tied[:, :, ::97], tied[:, :, 5::89] = np.inf, -np.inf
    out = []
    for s in (np.asarray(scores), tied):
        with jax.default_device(jax.devices("cpu")[0]):
            want = np.stack([np.asarray(lm_block.select_rows(layer, valid, k))
                             for layer in s])
        out.append([int((np.asarray(g(jnp.asarray(s), cursors))
                         != want).sum()) for g in (f, lines)])
    return out


def run_select(name, variants=SELECT_VARIANTS, calls=100, with_check=False,
               rehearse=False):
    """-> {"shape", "lanes", "rows", "k", "lanes_block", "<variant>": ms
    a selection, "check"}."""
    import jax

    from paddle_tpu.kernels import select_rows as sr

    shape = SHAPES[name]
    lanes, rows, k, cursors = select_geometry(shape, rehearse)
    layers = 2 if rehearse else SELECT_LAYERS
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse), "lanes": lanes, "rows": rows,
           "k": k, "valid_rows": int(cursors.sum() + lanes),
           "lanes_block": sr._lanes_block(lanes, rows)}
    passes = sr._PASSES
    for variant in variants:
        if rehearse and variant == "no_counts":
            continue    # the interpreter walks the whole kernel only
        jax.clear_caches()      # `_call` keeps the trace it made
        sr._PASSES = 0 if variant == "no_counts" else passes
        try:
            f, args = build_select(shape, sr, variant, rehearse)
            res[variant] = round(
                timed(f, args, 1 if rehearse else calls) / layers, 5)
        finally:
            sr._PASSES = passes
            jax.clear_caches()
        print(f"{name} {variant}", res[variant], flush=True)
    if with_check:
        res["check"] = check_select(shape, sr, rehearse)
    return res


def toy(shape):
    """`shape` cut to what the interpreter walks in seconds."""
    return dict(shape, slots=3, heads=min(shape["heads"], 8),
                row=128 if shape["d_value"] else 2 * shape["d_head"],
                d_value=shape["d_value"] and 128, ctx=256,
                layers=tuple((min(rows, 256), 1)
                             for rows, _ in shape["layers"]),
                mean_rows=90, select=shape.get("select") and 40,
                rows=min(shape.get("rows", 0), 256))


def run(name, block_sizes=None, variants=None, pa=None, calls=30,
        with_check=False, rehearse=False, order="consecutive",
        heads_blocks=(), pages=(), rows=0):
    """-> {"shape", "rows", "bs<n>.<variant>": ms, "bs<n>.pages",
    "bs<n>.tiling", "bs<n>.vmem_request", "bs<n>.check"}; with
    `pages` a reading a static cut, "bs<n>.p<pages>.<variant>".
    `rows`: every lane's cursor at so many (the shape's mix)."""
    import jax

    shape = SHAPES[name]
    if rows and "layers" in shape:
        shape = dict(shape, rows=rows)
    if not rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("kernel_pace: no TPU here; a time comes from a "
                         "chip run (--rehearse walks the script)")
    if shape.get("kernel") == "flash":
        return run_flash(name, variants or FLASH_VARIANTS, calls=calls,
                         with_check=with_check, rehearse=rehearse)
    if shape.get("kernel") == "delta":
        return run_delta(name, variants or DELTA_VARIANTS, heads_blocks,
                         calls=calls, with_check=with_check,
                         rehearse=rehearse)
    if shape.get("kernel") == "route":
        return run_route(name, variants or ROUTE_VARIANTS, calls=calls,
                         with_check=with_check, rehearse=rehearse)
    if shape.get("kernel") == "select":
        return run_select(name, variants or SELECT_VARIANTS, calls=calls,
                          with_check=with_check, rehearse=rehearse)
    if shape.get("kernel") == "index":
        return run_index(name, variants or VARIANTS, calls=calls,
                         with_check=with_check, rehearse=rehearse,
                         order=order)
    if pa is None:
        from paddle_tpu.kernels import paged_attention as pa
    real = shape
    if rehearse:
        shape, calls = toy(shape), 1
    lengths = lengths_of(shape)
    res = {"shape": name, "device": jax.devices()[0].device_kind,
           "rehearsal": bool(rehearse), "tables": order,
           "rows": int(sum(n * np.minimum(lengths, rows).sum()
                           for rows, n in shape["layers"]))}
    for bs in block_sizes or (shape["bs"],):
        res[f"bs{bs}.pages"] = int(sum(
            n * (-(-np.minimum(lengths, rows) // bs)).sum()
            for rows, n in shape["layers"]))
        for cut in pages or (0,):
            at = f"bs{bs}.p{cut}" if cut else f"bs{bs}"
            res[f"{at}.tiling"] = [
                list(selected(real, bs, pa, pages=cut).tiling(rows // bs))
                for rows, _ in real["layers"]]
            if rehearse:
                # of the REAL geometry: a lowering, nothing runs
                res[f"{at}.vmem_request"] = vmem_request(real, bs, pa, cut)
                print(f"{name} {at}.vmem_request",
                      res[f"{at}.vmem_request"], flush=True)
                # the toy's tables are shorter than the cut may be
                cut = min(cut, min(r for r, _ in shape["layers"]) // bs)
            for variant in variants or VARIANTS:
                if rehearse and variant != "whole":
                    continue    # the interpreter walks the whole kernel only
                res[f"{at}.{variant}"] = round(pace(
                    shape, bs, pa, variant, calls, rehearse, order, cut), 4)
                print(f"{name} {at}.{variant}", res[f"{at}.{variant}"],
                      flush=True)
            if with_check:
                res[f"{at}.check"] = check(shape, bs, pa, rehearse, order,
                                           cut)
    return res


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="deepseek-v2-serve-agent64",
                    help="a name of SHAPES, or several with commas "
                    "between (one process, a result each): "
                    + ", ".join(sorted(SHAPES)))
    ap.add_argument("--block-sizes", default="")
    ap.add_argument("--heads-blocks", default="",
                    help="the delta-rule kernel at so many heads a grid "
                    "step (its own choice)")
    ap.add_argument("--variants", default="",
                    help="of the shape's kernel's (all): "
                    + ",".join(VARIANTS + FLASH_VARIANTS[1:]
                               + DELTA_VARIANTS[2:] + ROUTE_VARIANTS[1:]
                               + SELECT_VARIANTS[2:]))
    ap.add_argument("--tables", default="consecutive", choices=TABLES)
    ap.add_argument("--pages", default="",
                    help="a paged-attention shape at each of these static "
                    "`pages` of `paged_attention`, one process (`tiling`'s)")
    ap.add_argument("--rows", type=int, default=0,
                    help="every lane's cursor at so many rows (the "
                    "shape's mix of cursors)")
    ap.add_argument("--calls", type=int, default=0,
                    help="calls a reading (30; a route or select shape's "
                    "100)")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    names = args.shape.split(",")
    for name in names:
        if name not in SHAPES:
            ap.error(f"--shape {name!r}: not one of SHAPES")
    res = [run(name,
               [int(b) for b in args.block_sizes.split(",") if b],
               tuple(v for v in args.variants.split(",") if v),
               calls=args.calls or (
                   100 if SHAPES[name].get("kernel") in ("route", "select")
                   else 30),
               with_check=args.check,
               rehearse=args.rehearse, order=args.tables,
               heads_blocks=[int(b) for b in args.heads_blocks.split(",")
                             if b],
               pages=[int(p) for p in args.pages.split(",") if p],
               rows=args.rows)
           for name in names]
    res = res[0] if len(res) == 1 else res
    print(json.dumps(res))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(res, fh)
    return res


if __name__ == "__main__":
    main()
