from bolt_tpu.ops.group import bincount, segment_reduce, topk, unique
from bolt_tpu.ops.hist import histogram
from bolt_tpu.ops.kernels import fused_welford, sepfilter1d
from bolt_tpu.ops.linalg import (corrcoef, cov, jacobi_eigh, lstsq, pca,
                                 svdvals, tallskinny_pca, tallskinny_svd,
                                 tsqr)
from bolt_tpu.ops.overlap import (convolve, gaussian, map_overlap,
                                  median_filter, smooth)
from bolt_tpu.ops.series import (center, crosscorr, detrend, fourier,
                                 normalize, zscore)
from bolt_tpu.ops import register

__all__ = ["bincount", "center", "convolve", "corrcoef", "cov",
           "crosscorr", "segment_reduce", "topk", "unique",
           "detrend", "fourier", "fused_welford", "gaussian", "sepfilter1d",
           "histogram", "jacobi_eigh", "lstsq", "map_overlap",
           "median_filter", "normalize", "pca", "register", "smooth", "svdvals",
           "tallskinny_pca", "tallskinny_svd", "tsqr", "zscore"]
