"""Test harness: 8 virtual CPU devices replace multi-chip hardware.

The reference uses k3d (Docker-in-Docker k8s) as its fake cluster
(SURVEY.md §4); here the fake backend is XLA's host-platform device count —
mesh/ppermute/psum tests run against 8 virtual CPU devices. Must be set
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import threading  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

# Honor SLT_FLIGHT for the suite the way the CLI does (obs/flight.py):
# a CI job exporting SLT_FLIGHT=<path> gets a causal event journal from
# the tests' own runtimes, dumped on any watchdog trip. Unset (the
# default) this returns None and the recorder stays off — the pinned
# bit-identity tests in tests/test_flight.py rely on that.
from split_learning_tpu.obs import flight as _obs_flight  # noqa: E402

_obs_flight.maybe_enable_from_env()


@pytest.fixture(scope="session", autouse=True)
def _lock_watchdog_gate():
    """Under SLT_LOCK_DEBUG=1 the runtime locks report inversions and
    hold-budget violations into obs/locks.py's default graph; any such
    report from the suite's own runtimes is a real bug — fail the
    session at teardown. (The intentional-inversion regression test
    uses a private LockGraph, so it never trips this gate.)"""
    from split_learning_tpu.obs import locks
    yield
    if locks.enabled():
        violations = locks.default_graph().violations
        assert not violations, (
            "lock watchdog reports from the test session:\n" +
            "\n".join(v["message"] for v in violations))


@pytest.fixture(scope="session", autouse=True)
def _dispatch_watchdog_gate():
    """Under SLT_DISPATCH_DEBUG=1 the runtimes run their jitted calls
    inside dispatch_debug step scopes; a steady-state recompile (local
    ordinal >= 2 with a previously-seen signature) or an unexpected-D2H
    report from the suite's own trainers is a real bug — fail the
    session at teardown. (Watchdog regression tests use private
    DispatchTracker instances, so they never trip this gate; arming is
    env-only — dispatch_debug.force() bench overrides don't count.)"""
    from split_learning_tpu.obs import dispatch_debug
    yield
    if os.environ.get("SLT_DISPATCH_DEBUG", "") not in ("", "0"):
        violations = dispatch_debug.tracker().violations
        assert not violations, (
            "dispatch watchdog reports from the test session:\n" +
            "\n".join(v["message"] for v in violations))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture()
def mnist_batch(rng):
    """A deterministic fake MNIST batch (reference batch size 64)."""
    kx, ky = jax.random.split(rng)
    x = jax.random.normal(kx, (64, 28, 28, 1), jnp.float32)
    y = jax.random.randint(ky, (64,), 0, 10)
    return x, y


class HoldHostGather:
    """Hold a party's replies in their materialization window.
    ``PartyRuntime._host_gather`` is the one call every reply's
    device-to-host copy goes through; inside the ``with`` block every
    call of it on ``runtime`` sets ``entered`` and waits for ``release``
    before it copies. The wrap lives on the instance; leaving the block
    lets go of whatever still waits and takes the wrap off. ``spawn``
    and ``join`` run the calls that are held: every wait is bounded by
    ``WAIT_S``, and none is expected to run out."""

    WAIT_S = 60.0

    @staticmethod
    def spawn(*fns):
        """One started thread a function."""
        threads = [threading.Thread(target=fn) for fn in fns]
        for t in threads:
            t.start()
        return threads

    @classmethod
    def join(cls, threads) -> None:
        for t in threads:
            t.join(cls.WAIT_S)
        assert not any(t.is_alive() for t in threads)

    def __init__(self, runtime) -> None:
        self.runtime = runtime
        self.entered = threading.Event()
        self.release = threading.Event()
        self.calls = 0

    def __enter__(self) -> "HoldHostGather":
        inner = self.runtime._host_gather

        def held(x, rows=None):
            self.calls += 1
            self.entered.set()
            assert self.release.wait(self.WAIT_S), "the copy was never let go"
            return inner(x, rows=rows)

        self.runtime._host_gather = held
        return self

    def __exit__(self, *exc) -> None:
        self.release.set()
        del self.runtime._host_gather  # back to the class's method


@pytest.fixture()
def hold_host_gather():
    """``with hold_host_gather(runtime) as hold:`` (see HoldHostGather)."""
    return HoldHostGather


@pytest.fixture()
def flash_tiled(monkeypatch):
    """``flash_tiled(q, k, v, block=, tile=, onepass=, ...)``: the flash
    kernels of ops/flash_attention.py built at a small ``block`` whose
    cut pairs work in sub-tiles of ``tile`` (the module's ``_TILE``, a
    constant of 256 on the chip), interpreted. ``[B, T, H, D]`` in and
    out (``v`` and the output may be of another width); with ``with_lse``
    also the ``[B, T, H]`` logsumexp."""
    import importlib
    fa = importlib.import_module("split_learning_tpu.ops.flash_attention")

    def call(q, k, v, *, block, tile, onepass, causal=True, window=None,
             strict=False, with_lse=False):
        monkeypatch.setattr(fa, "_TILE", tile)
        fa._make_flash.cache_clear()   # the edge is no part of its key
        b, t, h, d = q.shape
        d_v = v.shape[-1]
        fn = fa._make_flash(b * h, t, d, causal, str(q.dtype), block,
                            with_lse=with_lse, strict=strict,
                            onepass=onepass, window=window,
                            group=h // k.shape[2], d_v=d_v)
        fold = lambda x: jnp.transpose(x, (0, 2, 1, 3)).reshape(
            -1, t, x.shape[-1])
        out = fn(fold(q), fold(k), fold(v))
        o = out[0] if with_lse else out
        o = jnp.transpose(o.reshape(b, h, t, d_v), (0, 2, 1, 3))
        if with_lse:
            return o, jnp.transpose(out[1].reshape(b, h, t), (0, 2, 1))
        return o

    yield call
    fa._make_flash.cache_clear()
