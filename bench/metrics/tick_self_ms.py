"""``tick_self_ms``: mean over the window's ``serve.tick`` spans of the
duration less the ``serve.join`` and ``serve.step`` spans inside it: the
scheduler's own time per tick (placement, retirement, preemption checks,
telemetry), in ms."""
from bench import spans


def read(art):
    return spans.mean_less_children_ms(art, "serve.tick",
                                       ["serve.join", "serve.step"])
