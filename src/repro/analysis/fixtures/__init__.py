"""Known-bad entry points: the analyzer's positive controls.

Each builder here violates exactly ONE rule, so tests (and the runner's
control pass) can assert the rule fires there and nowhere on the
production registry.  None of these are registered in the global
registry — they are constructed on demand via :data:`FIXTURES`.

``badkernel/`` is a complete kernel package whose contract example
declares VMEM-hostile BlockSpecs; ``kernels.check_package("badkernel",
base="repro.analysis.fixtures")`` must flag it or the VMEM rule is
vacuous.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from ..registry import EntryPoint, OverlapSpec

__all__ = ["FIXTURES", "BAD_LINT_SRC", "BAD_SLEEP_SRC", "BAD_SERVER_SRC",
           "BADKERNEL_BASE"]

BADKERNEL_BASE = "repro.analysis.fixtures"

_L, _N, _PANELS = 16, 64, 3


def _mesh1() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _panel_loop(serialized: bool):
    """A miniature fused-panel loop over a 1-device mesh.  With
    ``serialized=True`` the per-panel norm psum consumes the freshly
    deflated shard (the hazard); otherwise it is issued from
    pre-deflation data (the double-buffered schedule)."""
    mesh = _mesh1()

    def body(z):
        norms = lax.psum(jnp.sum(z * z, axis=0), "data")     # prologue
        for _ in range(_PANELS):
            q = z[:, :4]
            w = q.T @ z
            if serialized:
                z = z - q @ w                  # deflate FIRST ...
                norms = lax.psum(jnp.sum(z * z, axis=0), "data")  # ... then reduce
            else:
                down = jnp.sum(w * w, axis=0)  # stage-A downdate only
                norms = lax.psum(jnp.sum(z * z, axis=0) - down, "data")
                z = z - q @ w                  # deflation overlaps the psum
        return z, norms

    fn = shard_map(body, mesh=mesh, in_specs=(P(None, "data"),),
                   out_specs=(P(None, "data"), P()), check_vma=False)
    return fn, (jax.ShapeDtypeStruct((_L, _N), jnp.float32),)


_OVERLAP = OverlapSpec(norm_shape=(_N,), deflate="sub",
                       deflate_shape=(_L, _N), expect_overlap=True)


def _gather_blowup():
    mesh = _mesh1()

    def body(z):
        full = lax.all_gather(z, "data", axis=1, tiled=True)  # l x n blowup
        return jnp.sum(full)

    fn = shard_map(body, mesh=mesh, in_specs=(P(None, "data"),),
                   out_specs=P(), check_vma=False)
    return fn, (jax.ShapeDtypeStruct((_L, _N), jnp.float32),)


def _f64_leak():
    def fn(x):
        return (x.astype(jnp.float64) @ x.astype(jnp.float64).T).sum()
    return fn, (jax.ShapeDtypeStruct((8, 8), jnp.float32),)


def _complex_truncation():
    def fn(x):
        return x.astype(jnp.float32) + 1.0     # drops the imaginary part
    return fn, (jax.ShapeDtypeStruct((8,), jnp.complex64),)


def _host_transfer():
    def fn(x):
        y = jax.device_put(x)
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), y)
    return fn, (jax.ShapeDtypeStruct((8,), jnp.float32),)


def _in_jit_timer():
    """A span timer planted INSIDE a jit boundary: reads the (sanctioned)
    obs clock through a callback mid-trace — exactly the instrumentation
    mistake ``repro.obs`` exists to prevent (spans open/close in host
    code AROUND jit).  The host-transfer rule must flag the callback, or
    in-jit timers could land in instrumented entry points unnoticed."""
    from ...obs.clock import now

    def fn(x):
        t = jax.pure_callback(lambda: np.float32(now()),
                              jax.ShapeDtypeStruct((), jnp.float32))
        return x * jnp.maximum(t, 1.0)
    return fn, (jax.ShapeDtypeStruct((8,), jnp.float32),)


FIXTURES = {
    "fixture.serialized-psum": EntryPoint(
        name="fixture.serialized-psum",
        build=lambda: _panel_loop(serialized=True),
        overlap=_OVERLAP, tags=("fixture",)),
    "fixture.overlapped-psum": EntryPoint(
        name="fixture.overlapped-psum",
        build=lambda: _panel_loop(serialized=False),
        overlap=_OVERLAP, tags=("fixture",)),
    "fixture.gather-blowup": EntryPoint(
        name="fixture.gather-blowup", build=_gather_blowup,
        max_collective_elems=_L * _N - 1, tags=("fixture",)),
    "fixture.f64-leak": EntryPoint(
        name="fixture.f64-leak", build=_f64_leak, tags=("fixture",)),
    "fixture.complex-truncation": EntryPoint(
        name="fixture.complex-truncation", build=_complex_truncation,
        tags=("fixture",)),
    "fixture.host-transfer": EntryPoint(
        name="fixture.host-transfer", build=_host_transfer,
        tags=("fixture",)),
    "fixture.in-jit-timer": EntryPoint(
        name="fixture.in-jit-timer", build=_in_jit_timer,
        tags=("fixture",)),
}

# For the lint tests: a file that trips every message rule exactly once.
BAD_LINT_SRC = '''\
import time
import numpy as np
import jax


def bad(kind, panel):
    if panel < 1:
        raise ValueError("bad panel")            # no value interpolated
    if kind == "a":
        out = 1
    elif kind == "b":
        out = 2
    elif kind == "c":
        out = 3
    else:
        raise ValueError(f"need l >= k, got l={panel} < k={panel}")
    jax.config.update("jax_enable_x64", True)
    t0 = time.time()
    noise = np.random.standard_normal(4)
    return out, t0, noise
'''

# For the time-sleep rule's control pair: a library module that blocks
# the host thread directly instead of waiting through an injected
# Clock.sleep.  Linted as ``runtime/bad_sleep.py`` the rule must fire;
# linted as ``obs/clock.py`` (the sanctioned implementation site) it
# must stay silent.
BAD_SLEEP_SRC = '''\
import time


def wait_for_chunk(delay):
    time.sleep(delay)
    return delay
'''

# For the socket-server rule's control pair: a library module that opens
# its own HTTP listener instead of going through the sanctioned
# telemetry endpoint.  Linted as ``serving/bad_server.py`` the rule must
# fire (once per banned import); linted as ``obs/telemetry.py`` (the one
# sanctioned server module) it must stay silent.
BAD_SERVER_SRC = '''\
import socket
from http.server import HTTPServer, BaseHTTPRequestHandler


def open_listener(port):
    srv = HTTPServer(("127.0.0.1", port), BaseHTTPRequestHandler)
    host = socket.gethostname()
    return srv, host
'''
