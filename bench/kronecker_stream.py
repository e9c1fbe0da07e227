"""The Graph500 Kronecker generator at scales the host's numpy generator
cannot reach in a run: drawn on the chips, in chunks, from ``--seed``.

The distribution is ``bench/graph.py``'s (Graph500 specification 3.0,
section 3.4): ``2**scale`` vertices, ``edgefactor · 2**scale`` tuples,
each placed by ``scale`` independent draws of a quadrant of the initiator
``[[A, B], [C, D]]`` (row: the source's bit, column: the destination's),
then the vertex labels permuted at random; self-loops and repeats stay,
tuple ``(i, j)`` is the directed edge ``i → j``, and the final shuffle of
the tuples' order is left out.  A quadrant is drawn from one 32-bit word
(``u < A·2**32`` is A, below ``(A+B)·2**32`` B, below ``(A+B+C)·2**32`` C,
else D) where ``bench/graph.py`` draws two floats, so the two generators
make different graphs from one seed; ``bench/graph.py`` keeps its stream
because the cells that use it are measured on it.

Chunk ``j`` of ``CHUNK`` tuples is drawn with ``jax.random`` (threefry,
the same bits on every backend) from the key of ``(seed, j)`` on device
``j mod len(devices)``, so what a seed gives does not depend on how many
devices draw it.  The permutation is drawn on the host
(``numpy.random.default_rng(seed)``) and applied on the devices.  Host
memory: the two int32 endpoint arrays (16 GiB at scale 27) and the
permutation.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 24  # tuples drawn per device call
IN_FLIGHT = 2  # chunks queued per device before the oldest is fetched


def _key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**64 (``--seed`` may pass 32
    signed bits)."""
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed >> 32)


def thresholds(initiator) -> tuple[int, int, int]:
    """The quadrant bounds on a uint32 word: A, A+B, A+B+C times 2**32."""
    a, b, c = initiator
    return tuple(int(round(x * 2**32)) for x in (a, a + b, a + b + c))


@functools.partial(jax.jit, static_argnames=("scale", "size", "bounds"))
def _draw(key, j, perm, *, scale: int, size: int, bounds):
    """Chunk ``j``: ``size`` tuples, labels mapped through ``perm`` (None
    leaves them as drawn)."""
    t_a, t_ab, t_abc = (jnp.uint32(t) for t in bounds)
    key = jax.random.fold_in(key, j)

    def level(lv, st):
        s, d = st
        u = jax.random.bits(jax.random.fold_in(key, lv), (size,), jnp.uint32)
        down = u >= t_ab  # C or D: the source's bit is 1
        right = ((u >= t_a) & (u < t_ab)) | (u >= t_abc)  # B or D
        return (s | (down.astype(jnp.int32) << lv),
                d | (right.astype(jnp.int32) << lv))

    zero = jnp.zeros((size,), jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (zero, zero))
    if perm is None:
        return s, d
    return perm[s], perm[d]


def edges(graph: dict, seed: int, devices=None, permute: bool = True
          ) -> tuple[int, np.ndarray, np.ndarray]:
    """``(n, src, dst)`` of the configuration's ``graph`` (``scale``,
    ``edgefactor``, ``initiator``), host int32 arrays, drawn on
    ``devices`` (default: the first device)."""
    scale = int(graph["scale"])
    n = 1 << scale
    m = int(graph["edgefactor"]) * n
    devices = list(devices or jax.devices()[:1])
    bounds = thresholds(graph["initiator"])
    key = _key(seed)
    perms = [None] * len(devices)
    if permute:
        host_perm = np.random.default_rng(int(seed) % 2**64).permutation(
            n).astype(np.int32)
        perms = [jax.device_put(host_perm, dev) for dev in devices]
        del host_perm
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    size = min(CHUNK, m)
    pending = collections.deque()

    def fetch():
        lo, (s, d) = pending.popleft()
        hi = min(lo + size, m)
        src[lo:hi] = np.asarray(s)[:hi - lo]
        dst[lo:hi] = np.asarray(d)[:hi - lo]

    for j, lo in enumerate(range(0, m, size)):
        i = j % len(devices)
        out = _draw(jax.device_put(key, devices[i]), j, perms[i],
                    scale=scale, size=size, bounds=bounds)
        for x in out:
            x.copy_to_host_async()
        pending.append((lo, out))
        if len(pending) > IN_FLIGHT * len(devices):
            fetch()
    while pending:
        fetch()
    return n, src, dst
