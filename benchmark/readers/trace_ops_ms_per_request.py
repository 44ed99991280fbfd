"""Device time per request inside operations whose trace name contains one
of ``match``, mean over the chips.  Nothing where no such operation ran."""


def read(ctx, match):
    if ctx["trace"] is None:
        return None
    seconds = sum(s for name, s in ctx["trace"]["ops_s"].items()
                  if any(m in name for m in match))
    if seconds == 0:
        return None
    return seconds / len(ctx["result"]["walls_s"]) * 1e3
