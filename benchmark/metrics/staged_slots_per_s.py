"""Slots the ingest pipeline staged on the device during the window, per
second; merged chunks and slots on an earlier line."""


def read(ctx):
    o, c = ctx["open"].get("pipeline"), ctx["close"].get("pipeline")
    if o is None or c is None:
        return None
    ctx["say"]("pipeline in the window: " + ", ".join(
        f"{k} {c[k] - o[k]}" for k in ("slots", "merged_slots",
                                        "merged_chunks") if k in c))
    return (c["slots"] - o["slots"]) / ctx["window_s"]
