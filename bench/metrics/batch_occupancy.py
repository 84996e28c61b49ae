"""Engine: active slots per decode tick over the engine's slots, mean
over the ticks that started in the window, in percent. Read from the
engine's slot table before each tick."""


def read(run):
    w0, w1 = run.window
    ticks = [t for t in run.ticks if w0 <= t.start < w1]
    if not ticks:
        return None
    return 100.0 * sum(len(t.contexts) for t in ticks) \
        / (len(ticks) * run.max_slots)
