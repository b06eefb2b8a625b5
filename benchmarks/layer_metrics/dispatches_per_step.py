"""``obs/dispatches`` of ``metrics.jsonl`` over the window, per epoch."""
LAYER, UNIT, SOURCE, MOVES = "step builder", "count", "program_counter", "images_per_s_per_chip"


def read(rec):
    by_epoch = {r["epoch"]: r for r in rec.rows}
    before, last = by_epoch.get(rec.first_epoch - 1), by_epoch.get(rec.last_epoch)
    if not before or not last or "obs/dispatches" not in last:
        return None
    return (last["obs/dispatches"] - before["obs/dispatches"]) / rec.epochs
