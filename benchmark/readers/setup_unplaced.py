"""What no counter of the program names: ``setup_s`` less the ``parts``
(each a ``counter_at_start`` reading: ``num``, ``less``).  The parts are
disjoint seconds of the set-up (the engine counts each as self time on its
thread), so this remainder is never negative: the imports made before the
program's own (jax, numpy), the benchmark's data and reference, the warm-up
requests' device time.  Nothing where the program lacks a counter."""


def read(ctx, parts):
    cell = ctx["cell"]
    at_start = cell.manifest.module("readers", "counter_at_start")
    placed = [at_start.read(ctx, **part) for part in parts]
    if cell.setup_s is None or None in placed:
        return None
    return cell.setup_s - sum(placed)
