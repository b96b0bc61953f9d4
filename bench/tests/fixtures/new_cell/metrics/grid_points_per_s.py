"""Grid points decoded per second of the window: each visit answers its labels at Q points."""


def read(rec):
    return rec["labels"] / rec["window_s"] if rec.get("window_s") else None
