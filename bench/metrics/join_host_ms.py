"""``join_host_ms``: mean over the window's ``serve.join`` spans of the
duration less its ``serve.join.wait`` (the fetch of the first token, the
join's one wait on the device): the host's part of a join, in ms."""
from bench import spans


def read(art):
    return spans.mean_less_children_ms(art, "serve.join", ["serve.join.wait"])
