"""The least stream's completed requests over the mean of the streams'
(``result["stream_counts"]``, a driver of several callers): 1.0 where the
queue serves every caller alike, and what the configuration's no-starvation
guarantee puts a floor under.  Nothing where the driver ran one caller."""


def read(ctx):
    counts = ctx["result"].get("stream_counts")
    if not counts or not sum(counts):
        return None
    return min(counts) / (sum(counts) / len(counts))
