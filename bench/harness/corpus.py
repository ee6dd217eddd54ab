"""The corpus, its PCA cut and its index, made on the device from the seed.

The generative model is the TAS-B profile of ``repro.data.synthetic``
(``corpus_blocks``), copied here so that the yardstick does not move with
the program:

    z ~ N(0, diag(lam)),  lam_j = j**-alpha / sum_j j**-alpha
    x = normalize(z @ F.T + sigma / sqrt(d) * eps),  F orthonormal

``F`` is drawn on the host from the seed (one d x d QR); every row block is
drawn on the device from a key folded with its global block number, so a
layout of one chip or of four row shards holds the same corpus for one
seed. Each stored block comes out of one jitted program (draw, project,
quantise), and the reference regenerates a block by calling the same
program again: it reads the rows the index was built from without reading
the index.
"""
from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed31(*parts) -> int:
    """A 31-bit PRNG seed from ints and strings, the same in every process."""
    key = "\x1f".join(map(str, parts)).encode()
    return int.from_bytes(hashlib.blake2b(key, digest_size=4).digest(),
                          "little") & 0x7FFFFFFF


@partial(jax.jit, static_argnames=("rows",))
def draw(key, b, Ft, sd, noise_sd, rows: int):
    """(rows, d) unit rows of block ``b`` of the stream ``key``."""
    kz, ke = jax.random.split(jax.random.fold_in(key, b))
    d = Ft.shape[0]
    z = jax.random.normal(kz, (rows, d), jnp.float32) * sd[None, :]
    eps = jax.random.normal(ke, (rows, d), jnp.float32)
    x = jnp.dot(z, Ft, precision=HIGHEST) + noise_sd * eps
    return x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-9)


@partial(jax.jit, donate_argnums=0)
def _gram_acc(G, x):
    return G + jnp.dot(x.T, x, precision=HIGHEST)


@partial(jax.jit, static_argnames=("qmax",))
def stored_rows(x, W, scale, qmax: int):
    """Rows as the index stores them: ``x @ W`` (when pruned), then
    symmetric per-dimension quantisation to ``[-qmax, qmax]`` (when
    ``scale`` is given) with the round and clip of the program's
    ``quantize_int8_per_dim``."""
    p = x if W is None else jnp.dot(x, W, precision=HIGHEST)
    if scale is None:
        return p
    return jnp.clip(jnp.round(p / scale[None, :]), -qmax, qmax).astype(
        jnp.int8)


@partial(jax.jit, donate_argnums=0)
def _absmax_acc(a, x, W):
    return jnp.maximum(a, jnp.max(jnp.abs(jnp.dot(x, W, precision=HIGHEST)),
                                  axis=0))


@partial(jax.jit, donate_argnums=0, static_argnames=("lo", "length"))
def _fill(buf, rows, dst, lo: int, length: int):
    """Write ``rows[lo:lo + length]`` at row ``dst`` of ``buf``, in place."""
    return jax.lax.dynamic_update_slice_in_dim(
        buf, rows[lo:lo + length].astype(buf.dtype), dst, axis=0)


class Corpus:
    """One configuration's corpus for one seed: its blocks, its PCA cut and
    int8 scale (when the configuration has them), and its queries."""

    def __init__(self, config: dict, seed: int):
        self.n = int(config["rows"])
        self.d = int(config["d"])
        self.m = config.get("m")
        self.block = int(config["block_rows"])
        self.int8 = config["dtype"] == "int8"
        prof = config["profile"]
        lam = np.arange(1, self.d + 1, dtype=np.float64) ** (-prof["alpha"])
        self._sd = np.sqrt(lam / lam.sum()).astype(np.float32)
        F, _ = np.linalg.qr(np.random.default_rng(
            seed31("basis", seed)).standard_normal((self.d, self.d)))
        self._Ft = np.ascontiguousarray(F.T, np.float32)
        self._noise = np.float32(prof["sigma"] / np.sqrt(self.d))
        self._seeds = dict(block=seed31("block", seed),
                           query=seed31("query", seed))
        self._on: dict = {}
        self.components = None   # (d, d) float32, host, by eigenvalue
        self.eigenvalues = None  # (d,) float32, host, descending
        self.W = None            # (d, m) leading components
        self.scale = None        # (m,) float32, host
        self.qmax = 127

    @property
    def nblocks(self) -> int:
        return -(-self.n // self.block)

    @property
    def width(self) -> int:
        return self.d if self.m is None else int(self.m)

    @property
    def dtype(self):
        return jnp.int8 if self.int8 else jnp.float32

    def _args(self, device, stream: str = "block"):
        """The generator's operands, committed to ``device``."""
        key = (device, stream)
        if key not in self._on:
            put = partial(jax.device_put, device=device)
            self._on[key] = (put(jax.random.key(self._seeds[stream])),
                             put(self._Ft), put(self._sd), put(self._noise))
        return self._on[key]

    def raw(self, b: int, device, stream: str = "block"):
        key, Ft, sd, noise = self._args(device, stream)
        return draw(key, np.int32(b), Ft, sd, noise, rows=self.block)

    def rows(self, b: int, device, *, scale=None, qmax=None):
        """Block ``b`` as stored (all ``block`` rows; the last block's
        rows past ``n`` are not part of the corpus)."""
        W = None if self.W is None else self._put(self.W, device)
        s = self.scale if scale is None else scale
        s = None if not self.int8 else self._put(s, device)
        return stored_rows(self.raw(b, device), W, s,
                           qmax=self.qmax if qmax is None else qmax)

    def _put(self, a, device):
        key = (device, id(a))
        if key not in self._on:
            self._on[key] = (a, jax.device_put(a, device))
        return self._on[key][1]

    def _drawn(self, device):
        """Every block as drawn, the last one cut to the corpus."""
        for b in range(self.nblocks):
            x = self.raw(b, device)
            tail = self.n - b * self.block
            yield x if tail >= self.block else x[:tail]

    def fit(self, device) -> None:
        """The paper's cut: eigendecomposition of the uncentered Gram
        ``D^T D``, the ``m`` leading eigenvectors; then the int8 scale of
        the projected corpus. Two passes over the corpus on the device."""
        if self.m is None:
            return
        G = jax.device_put(jnp.zeros((self.d, self.d), jnp.float32), device)
        for x in self._drawn(device):
            G = _gram_acc(G, x)
        evals, evecs = np.linalg.eigh(np.asarray(G, np.float64))
        order = np.argsort(evals)[::-1]
        self.eigenvalues = np.clip(evals[order], 0, None).astype(np.float32)
        self.components = evecs[:, order].astype(np.float32)
        self.W = np.ascontiguousarray(self.components[:, :int(self.m)])
        if not self.int8:
            return
        a = jax.device_put(jnp.zeros((self.width,), jnp.float32), device)
        W = self._put(self.W, device)
        for x in self._drawn(device):
            a = _absmax_acc(a, x, W)
        self.scale = (np.maximum(np.asarray(a), 1e-12) / 127.0).astype(
            np.float32)

    def fill(self, bufs: list[tuple]) -> list:
        """Fill preallocated row buffers in place: ``bufs`` holds
        ``(device, buffer, lo, hi)`` for the global rows ``[lo, hi)`` each
        buffer holds from its row 0 (rows past ``n`` stay zero). Each
        device writes its j-th block while the others write theirs, and
        every step waits for its writes, so no device queues more than
        one block."""
        plans = []
        for device, _, lo, hi in bufs:
            plan = []
            top = min(hi, self.n)
            for b in range(lo // self.block, -(-top // self.block)):
                g0 = b * self.block
                a, z = max(lo, g0), min(top, g0 + self.block)
                if a < z:
                    plan.append((b, a - g0, a - lo, z - a))
            plans.append(plan)
        out = [buf for _, buf, _, _ in bufs]
        for j in range(max(map(len, plans))):
            for i, (device, _, _, _) in enumerate(bufs):
                if j < len(plans[i]):
                    b, src, dst, length = plans[i][j]
                    out[i] = _fill(out[i], self.rows(b, device),
                                   np.int32(dst), lo=src, length=length)
            jax.block_until_ready(out)
        return out

    def queries(self, count: int, device) -> np.ndarray:
        """(count <= block, d) raw float32 queries from the seed's query
        stream."""
        return np.asarray(self.raw(0, device, stream="query")[:count])
