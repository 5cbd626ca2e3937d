"""A percentile of the gaps between consecutive output tokens, over
all tokens of all the window's requests.  Every token after a stream's
first takes the arrival time of the event that carried it, so chunked
delivery shows as its chunk gap (the tokens inside a chunk are 0 apart)."""

from cellbench import reduce


def read(ctx, q: float, scale: float = 1000.0):
    gaps = [g for r in ctx.records if not reduce.failed(r, True)
            for g in reduce.token_gaps(r)]
    if not gaps:
        return None
    chunk_gaps = [g for g in gaps if g > 0.0]
    ctx.notes["token_gap_s"] = {
        "median": reduce.median(gaps), "n": len(gaps),
        f"p{round(q * 100)}": reduce.pctile(gaps, q),
        "chunk_gap_median": reduce.median(chunk_gaps) if chunk_gaps else None,
        "chunk_gaps": len(chunk_gaps)}
    return reduce.pctile(gaps, q) * scale
