"""The keys a query of a block-sparse layer visits, over the causal keys
dense attention would visit (``(T + 1) / 2`` a query on average): from
the ``attn.select`` records the step keeps while the recorder is armed
(``decoder.selection_detail``: per such layer the mean over the batch's
queries and K/V heads of the causal keys in its selected blocks).  The
mean over the traced steps and layers.

Log line: ``sparse_tile_pairs``, the (query tile, key tile) pairs the
kernels walked a K/V head beside the causal pairs (what the tiles cost,
where this metric reads what the selection needs)."""

NAME, UNIT = "sparse_visit_share", "%"
LAYER = "attention kernel"
MOVES = "fed_items_per_s"
SOURCE = "program_counter"
CELLS = ["minicpm-sala-d4.*"]


def read(ctx):
    records = [r.detail for r in getattr(ctx, "recorder_records", ())
               if r.phase == "attn.select" and r.detail]
    rows = [(d, row) for d in records for row in d.get("layers", ())]
    if not rows:
        return None
    pairs = [row["pairs"] for _, row in rows if "pairs" in row]
    if pairs:
        from benchmark.reduce import log

        log(sparse_tile_pairs={"walked": sum(pairs) / len(pairs),
                               "causal": rows[0][0].get("causal_pairs")})
    return 100.0 * sum(
        row["visited_keys"] / d["dense_keys"] for d, row in rows
    ) / len(rows)
