"""Incremental set hash (paper S8.1) and per-key commutative hashes (S8.2).

The paper XORs SHA-1 digests of <deadline, client-id, request-id> to maintain
a running hash over the *set* of log entries; because logs are always ordered
by deadline, set equality implies sequence equality. We keep the identical
XOR-incremental algebra but swap the digest:

* Python/NumPy protocol path: 64-bit splitmix64-based entry hash (drop-in
  spot for SHA-1 in a real deployment).
* JAX / Pallas path: 32-bit murmur3-finalizer entry hash. TPUs have no native
  64-bit integer datapath, so the hardware-adapted kernel folds uint32 lanes
  (this is a deliberate TPU adaptation, recorded in DESIGN.md). A NumPy
  mirror (`entry_hash32_np`) is provided and tests assert bit-equality
  between the NumPy mirror, the jnp implementation, and the Pallas kernel.

The crash-vector hash is XORed into every fast-reply hash (S8.1 / SA.4) to
defeat stray fast-replies after crash-recovery.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

_MASK64 = np.uint64(0xFFFFFFFFFFFFFFFF)


# ---------------------------------------------------------------------------
# 64-bit path (Python protocol implementation)
# ---------------------------------------------------------------------------
def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    """Deterministic 64-bit mix (splitmix64 finalizer)."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = (x + np.uint64(0x9E3779B97F4A7C15)) & _MASK64
        z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & _MASK64
        z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & _MASK64
        z = z ^ (z >> np.uint64(31))
    return z


def entry_hash_np(deadline_ns: np.ndarray, client_id: np.ndarray, request_id: np.ndarray) -> np.ndarray:
    """h(request): mixes the 3-tuple <deadline, client-id, request-id> (S8.1)."""
    with np.errstate(over="ignore"):
        d = _splitmix64_np(np.asarray(deadline_ns, dtype=np.uint64))
        c = _splitmix64_np(np.asarray(client_id, dtype=np.uint64) ^ np.uint64(0xA5A5A5A5A5A5A5A5))
        r = _splitmix64_np(np.asarray(request_id, dtype=np.uint64) ^ np.uint64(0x5A5A5A5A5A5A5A5A))
        return _splitmix64_np(d ^ ((c * np.uint64(0x100000001B3)) & _MASK64) ^ r)


def fold_hashes_np(hashes: np.ndarray) -> np.uint64:
    """XOR-fold a set of entry hashes -> running set hash H_n."""
    h = np.asarray(hashes, dtype=np.uint64)
    if h.size == 0:
        return np.uint64(0)
    return np.bitwise_xor.reduce(h.ravel())


def crash_vector_hash_np(cv: Sequence[int]) -> np.uint64:
    """h(crash-vector): mix each counter with its index, fold (SA)."""
    cv = np.asarray(cv, dtype=np.uint64)
    idx = np.arange(cv.size, dtype=np.uint64)
    return fold_hashes_np(_splitmix64_np(cv ^ _splitmix64_np(idx)))


class IncrementalHash:
    """The running hash a replica maintains: add/remove entries in O(1)."""

    def __init__(self, crash_vector: Sequence[int] | None = None):
        self._h = np.uint64(0)
        self._cv_h = np.uint64(0)
        if crash_vector is not None:
            self.set_crash_vector(crash_vector)

    def set_crash_vector(self, cv: Sequence[int]) -> None:
        self._cv_h = crash_vector_hash_np(cv)

    def add(self, deadline_ns: int, client_id: int, request_id: int) -> None:
        self._h ^= entry_hash_np(np.uint64(deadline_ns), np.uint64(client_id), np.uint64(request_id))

    # XOR is its own inverse: removal == addition.
    remove = add

    @property
    def value(self) -> int:
        """hash_n = H_n xor h(crash-vector)."""
        return int(self._h ^ self._cv_h)

    @property
    def set_hash(self) -> int:
        return int(self._h)

    def copy(self) -> "IncrementalHash":
        out = IncrementalHash()
        out._h = self._h
        out._cv_h = self._cv_h
        return out


class PerKeyHashTable:
    """Commutativity optimization (S8.2): one running hash per written key.

    fast-reply for a request touching keys K carries XOR of the per-key
    hashes for K only; reads contribute nothing.
    """

    def __init__(self):
        self._table: dict[int, np.uint64] = {}

    def add_write(self, key: int, deadline_ns: int, client_id: int, request_id: int) -> None:
        h = entry_hash_np(np.uint64(deadline_ns), np.uint64(client_id), np.uint64(request_id))
        self._table[key] = self._table.get(key, np.uint64(0)) ^ h

    remove_write = add_write

    def reply_hash(self, keys: Iterable[int]) -> int:
        h = np.uint64(0)
        for k in set(keys):
            h ^= self._table.get(k, np.uint64(0))
        return int(h)

    def copy(self) -> "PerKeyHashTable":
        out = PerKeyHashTable()
        out._table = dict(self._table)
        return out


# ---------------------------------------------------------------------------
# key -> group routing (sharded Nezha)
# ---------------------------------------------------------------------------
_GROUP_SALT = np.uint64(0xC0FFEE5EED5EED00)


def key_group_np(keys: np.ndarray, n_groups: int) -> np.ndarray:
    """Deterministic key -> consensus-group routing for sharded Nezha.

    Routes through the same splitmix64 mix the set hashes use -- NOT the
    builtin ``hash()``, whose value varies with PYTHONHASHSEED -- so the
    assignment is identical across processes, restarts, and platforms.
    The salt decorrelates routing from the entry-hash algebra (a key's
    group says nothing about its log hash). ``n_groups`` = 1 maps all keys
    to group 0 (the unsharded identity).
    """
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    keys = np.asarray(keys, dtype=np.uint64)
    if n_groups == 1:
        return np.zeros(keys.shape, dtype=np.int64)
    h = _splitmix64_np(keys ^ _GROUP_SALT)
    # 64x32-bit multiply-shift range reduction: unbiased enough for routing
    # and avoids the modulo's low-bit correlation with sequential keys.
    with np.errstate(over="ignore"):
        g = (h >> np.uint64(32)) * np.uint64(n_groups) >> np.uint64(32)
    return g.astype(np.int64)


def key_group(key: int, n_groups: int) -> int:
    """Scalar convenience form of `key_group_np`."""
    return int(key_group_np(np.uint64(key), n_groups))


# ---------------------------------------------------------------------------
# 32-bit path (JAX + Pallas; TPU has no native 64-bit integer datapath)
# ---------------------------------------------------------------------------
_MASK32 = np.uint32(0xFFFFFFFF)


def _murmur32_np(x: np.ndarray) -> np.ndarray:
    """murmur3 fmix32 finalizer."""
    x = np.asarray(x, dtype=np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint32(16))
        x = (x * np.uint32(0x85EBCA6B)) & _MASK32
        x = x ^ (x >> np.uint32(13))
        x = (x * np.uint32(0xC2B2AE35)) & _MASK32
        x = x ^ (x >> np.uint32(16))
    return x


def entry_hash32_np(deadline_ns: np.ndarray, client_id: np.ndarray, request_id: np.ndarray) -> np.ndarray:
    """32-bit mirror of the kernel/jnp entry hash (same algebra as 64-bit)."""
    with np.errstate(over="ignore"):
        d = _murmur32_np(np.asarray(deadline_ns, dtype=np.uint32))
        c = _murmur32_np(np.asarray(client_id, dtype=np.uint32) ^ np.uint32(0xA5A5A5A5))
        r = _murmur32_np(np.asarray(request_id, dtype=np.uint32) ^ np.uint32(0x5A5A5A5A))
        return _murmur32_np(d ^ ((c * np.uint32(0x01000193)) & _MASK32) ^ r)


def _murmur32_jnp(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def entry_hash_jnp(deadline_ns, client_id, request_id):
    """Vectorized h(request); bit-identical to entry_hash32_np."""
    d = _murmur32_jnp(jnp.asarray(deadline_ns).astype(jnp.uint32))
    c = _murmur32_jnp(jnp.asarray(client_id).astype(jnp.uint32) ^ jnp.uint32(0xA5A5A5A5))
    r = _murmur32_jnp(jnp.asarray(request_id).astype(jnp.uint32) ^ jnp.uint32(0x5A5A5A5A))
    return _murmur32_jnp(d ^ (c * jnp.uint32(0x01000193)) ^ r)


def fold_hashes_jnp(hashes):
    """XOR-fold -> H_n over a whole set."""
    h = jnp.asarray(hashes).astype(jnp.uint32)
    return jax.lax.reduce(h.ravel(), jnp.uint32(0), jax.lax.bitwise_xor, (0,))


def prefix_hashes_jnp(hashes):
    """hash_i for every prefix (what the i-th fast-reply carries)."""
    return jax.lax.associative_scan(jnp.bitwise_xor, jnp.asarray(hashes).astype(jnp.uint32))


def crash_vector_hash_jnp(cv):
    cv = jnp.asarray(cv).astype(jnp.uint32)
    idx = jnp.arange(cv.shape[-1], dtype=jnp.uint32)
    return fold_hashes_jnp(_murmur32_jnp(cv ^ _murmur32_jnp(idx)))


__all__ = [
    "entry_hash_np",
    "fold_hashes_np",
    "key_group_np",
    "key_group",
    "crash_vector_hash_np",
    "IncrementalHash",
    "PerKeyHashTable",
    "entry_hash32_np",
    "entry_hash_jnp",
    "fold_hashes_jnp",
    "prefix_hashes_jnp",
    "crash_vector_hash_jnp",
]
