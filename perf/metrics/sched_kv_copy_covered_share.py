"""K/V pages whose copy the attention kernel's products can hide, of the
pages the dispatched decode steps read: 100 x the sum of `kv_pages_covered`
over the sum of `kv_pages_read` on the program's `serving.decode_tick` spans
of the window that carry both (summed over slots and attention layers).
`kv_pages_covered` is `decoder.tick_counts`'s account of the page stream
(`kernels.paged_attention.stream_counts`): a chunk's copy is in flight while
the chunk BEFORE it in the stream is multiplied and under nothing else, so
every chunk copy of a call but its first counts `min(its pages, the pages of
the row window multiplied meanwhile)`.  100 means every copy has as many
pages' products to hide under (equal chunks, or one chunk a slot); a ring of
33 pages cut 28 + 5 reads (5 + 8) / 33 = 39: the 28-page copy waits under
the 8-page window of the five pages before it.  It says how often the cut
engages, not whether the products are long enough: a kernel paced by bytes
reads 100 and gains nothing.  Nothing where the program sets no such
attribute (a parent before PR 66, the gather path) or keeps no span store
under a listener."""
LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def compute(run):
    from paddle_tpu.observability import tracing

    if not run.spans:
        return None
    lo, hi = (s["ts"] + s["dur"] for s in (run.spans[0], run.spans[-1]))
    ticks = [s["attrs"] for s in tracing.finished_spans()
             if s["name"] == "serving.decode_tick"
             and lo <= s["ts"] + s["dur"] <= hi
             and "kv_pages_covered" in s["attrs"]]
    pages = sum(a["kv_pages_read"] for a in ticks)
    return (100.0 * sum(a["kv_pages_covered"] for a in ticks) / pages
            if pages else None)
