"""The serve layer's cross-program parity contract as an assertion (see
``xgboost_ray_tpu/serve/predictor.py``): integer outputs agree exactly,
float outputs to ``PARITY_ULPS`` float32 ulps."""

import numpy as np

from xgboost_ray_tpu.serve.predictor import parity_atol


def assert_parity(got, ref, what=""):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.dtype == ref.dtype and got.shape == ref.shape, what
    if np.issubdtype(ref.dtype, np.integer):
        assert np.array_equal(got, ref), what
    else:
        np.testing.assert_allclose(
            got, ref, rtol=0, atol=parity_atol(ref), err_msg=str(what)
        )
