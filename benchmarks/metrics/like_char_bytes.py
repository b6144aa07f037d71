"""Character bytes per completed query that LIKE predicates read:
capacity x ``char_cap`` of every LIKE a dispatched filter or fused stage
evaluates, the engine's ``likeCharBytes`` (``ops/exprs.py``, from the
program's static shapes, no device read). A program without the counter
reports nothing."""


def read(window):
    if "likeCharBytes" not in window.counters:
        return None
    return window.per_query("likeCharBytes")
