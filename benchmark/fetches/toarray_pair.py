"""The two maps of ``steps/tuning_map.py`` as the caller holds them: each
``(height, width)`` result of ``bolt.ops.fourier`` brought to the host by
``toarray()``, the public way to hold it (``fetches/toarray.py`` takes one
handle; this takes the pair ``fourier`` returns)."""

import numpy as np

ON_DEVICE = False


def take(handle):
    coherence, phase = handle
    return {"coherence": np.asarray(coherence.toarray()),
            "phase": np.asarray(phase.toarray())}
