"""CPU seconds of the whole process (``time.process_time()``, all
threads) over the wall seconds of the window: how many host cores the
program keeps busy while it drives the chip. Layer: app loop. Moves
``train_items_per_s``."""


def read(run):
    w = run.window
    return w["cpu_s"] / w["wall_s"] if w.get("wall_s") else None
