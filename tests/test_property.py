"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.dma import HybridMover
from repro.core.hlo import _link_bytes, dtype_bytes
from repro.optim import compress_int8, decompress_int8
from repro.runtime.fault_tolerance import plan_elastic_mesh
from repro.configs.base import pad_to_multiple

SET = dict(max_examples=50, deadline=None)


@given(st.integers(1, 1 << 30), st.integers(1, 4096))
@settings(**SET)
def test_pad_to_multiple_properties(x, m):
    p = pad_to_multiple(x, m)
    assert p % m == 0
    assert 0 <= p - x < m


@given(st.integers(0, 1 << 24), st.integers(0, 1 << 24), st.integers(1, 512))
@settings(**SET)
def test_link_bytes_nonnegative_and_bounded(res, opr, n):
    for op in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute"):
        lb = _link_bytes(op, res, opr, n)
        assert lb >= 0
        assert lb <= 2 * max(res, opr)  # never more than 2x the buffer


@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                min_size=1, max_size=512))
@settings(**SET)
def test_int8_compression_bounded_error(xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    q, s = compress_int8(x)
    y = decompress_int8(q, s, x.shape, jnp.float32)
    # per-block max error <= scale/2 ~ max|block|/254 (+eps guard)
    err = np.max(np.abs(np.asarray(y) - np.asarray(x)))
    bound = max(1e-9, np.max(np.abs(np.asarray(x)))) / 127.0 + 1e-6
    assert err <= bound


@given(st.integers(1, 4096),
       st.lists(st.sampled_from([64, 128, 1408, 4096, 14336, 16384, 53248]),
                min_size=1, max_size=4))
@settings(**SET)
def test_elastic_mesh_always_valid(n_devices, dims):
    data, model = plan_elastic_mesh(n_devices, dims)
    assert data >= 1 and model >= 1
    assert data * model <= n_devices
    assert all(d % model == 0 for d in dims)


@given(st.integers(1, 1 << 20), st.integers(0, 1 << 22))
@settings(**SET)
def test_hybrid_mover_mode_is_threshold_function(threshold, nbytes):
    mover = HybridMover(threshold=threshold)
    x = np.zeros(max(1, nbytes), np.uint8)
    _, rec = mover.put(x)
    assert rec.mode == ("inline" if x.nbytes < threshold else "direct")


@given(st.integers(1, 1 << 20))
@settings(**SET)
def test_hybrid_mover_direct_at_exact_threshold(nbytes):
    """Boundary law: a payload of exactly threshold bytes goes direct."""
    _, rec = HybridMover(threshold=nbytes).put(np.zeros(nbytes, np.uint8))
    assert rec.mode == "direct"


#: a dispatch time a timer reads: a whole number of nanoseconds, 1 ns to 10 s
_DISPATCH_S = st.integers(1, 10 ** 10).map(lambda ns: ns * 1e-9)


@given(_DISPATCH_S, _DISPATCH_S, st.floats(0, 5, allow_nan=False),
       st.integers(0, 1000), st.integers(1, 1 << 16))
@settings(**SET)
def test_objective_monotone_in_dispatch_time(d1, d2, transfer_s, doorbells,
                                             tokens):
    """The tuner objective must strictly order by measured dispatch time
    when everything else is equal — otherwise search tunes the wrong way.
    Times are drawn on a nanosecond grid, as a timer reads them: two
    distinct readings then differ by at least 1e-9 s, which the weighted
    cost (at most about 15 s) and its division by the token count resolve,
    where a denormal such as 5e-324 vanishes into the other terms."""
    from repro.tune import Metrics, Objective
    obj = Objective()
    lo = Metrics(dispatch_s=min(d1, d2), transfer_s=transfer_s,
                 doorbells=doorbells, tokens=tokens)
    hi = Metrics(dispatch_s=max(d1, d2), transfer_s=transfer_s,
                 doorbells=doorbells, tokens=tokens)
    if d1 == d2:
        assert obj.score(lo) == obj.score(hi)
    else:
        assert obj.score(lo) < obj.score(hi)


@given(st.sampled_from(["f32", "bf16", "f16", "s8", "u32", "pred", "f64"]))
@settings(**SET)
def test_dtype_bytes_known(d):
    assert dtype_bytes(d) > 0
