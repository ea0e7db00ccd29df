"""Host milliseconds per engine step spent outside the backend: the wall
time of each ``Engine.step`` started in the window, less the time inside
the backend's hooks (device work and its waits) during that step,
averaged over those steps. This is the engine and both schedulers."""
from stats import spans


def read(ctx):
    steps = spans(ctx, "engine.step")
    hooks = [(s, e) for _, s, e in spans(ctx, "backend.")]
    if not steps:
        return None
    own = 0.0
    for _, s, e in steps:
        inner = sum(min(he, e) - max(hs, s) for hs, he in hooks
                    if hs < e and he > s)
        own += (e - s) - inner
    return 1e3 * own / len(steps)
