"""The host's own part of a request: seconds of the traced window in which
the device ran nothing while one of the named benchmark spans was open (the
idle gaps that ``tracered`` gives to those spans), per request.  The spans'
whole host-clock length would count the wait for the device too."""


def read(ctx, spans):
    t = ctx["trace"]
    if t is None:
        return None
    gaps = t["idle_gaps_s"]
    return sum(gaps.get(s, 0.0) for s in spans) \
        / len(ctx["result"]["walls_s"]) * 1e3
