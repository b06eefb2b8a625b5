"""What of ``setup_s`` is still under no span of the program: the seconds between
process start (the harness's stamp) and the window's opening that no depth-0 span
of ``trace.jsonl`` covers, plus the seconds of the warm-up ``epoch`` spans that none
of their direct children covers. The notes list the five longest such gaps with
the spans on either side."""
LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def gaps(lo, hi, spans, first, last):
    """The stretches of ``[lo, hi]`` under none of ``spans``, as ``(seconds,
    span before, span after)``; ``first`` and ``last`` name the two ends."""
    out, at, before = [], lo, first
    for s in sorted(spans, key=lambda s: s["t0"]):
        if s["t1"] < lo or s["t0"] > hi:
            continue
        if s["t0"] > at:
            out.append((s["t0"] - at, before, s["name"]))
        if s["t1"] >= at:
            at, before = s["t1"], s["name"]
    if hi > at:
        out.append((hi - at, before, last))
    return out


def read(rec):
    if not rec.spans or not rec.t_open:
        return None
    top = [s for s in rec.spans if s["depth"] == 0]
    found = gaps(rec.t_process_start, rec.t_open, top, "process start", "window open")
    for e in (s for s in top if s["name"] == "epoch" and s["t0"] < rec.t_open):
        children = [s for s in rec.spans if s["depth"] == 1 and s.get("parent") == "epoch"
                    and e["t0"] <= s["t0"] and s["t1"] <= e["t1"]]
        found += gaps(e["t0"], min(e["t1"], rec.t_open), children, "epoch start", "window open")
    for seconds, before, after in sorted(found, reverse=True)[:5]:
        rec.notes.append(f"under no span: {seconds:.3f} s between {before} and {after}")
    return sum(g[0] for g in found)
