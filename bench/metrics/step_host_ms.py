"""``step_host_ms``: mean over the window's ``serve.step`` spans of the
duration less its ``serve.step.wait`` (the fetch of the step's tokens, its
one wait on the device): the host's part of a decode step, in ms."""
from bench import spans


def read(art):
    return spans.mean_less_children_ms(art, "serve.step", ["serve.step.wait"])
