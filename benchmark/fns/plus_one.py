"""``v + 1``: the BASELINE metric's map body."""

REACH = 1            # how far the body can move a value's magnitude


def body(v):
    """What the program maps (module-level: one engine program)."""
    return v + 1


def reference(x):
    """Spelled again for the reference, which shares nothing with it."""
    return x + 1
