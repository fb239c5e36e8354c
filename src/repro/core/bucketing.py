"""Bucketing (Karimireddy et al. 2022) — the randomized baseline the paper
compares against (and outperforms; see paper Appendix 10), and the
pre-reduction stage of the hierarchical aggregation path
(``AggregatorSpec.hier`` / ``backend="pallas_hier"``).

Randomly permutes the n inputs, averages consecutive groups of size s, and
feeds the ceil(n/s) bucket means to the downstream rule with an adjusted
Byzantine count.  The heterogeneity reduction holds only in expectation over
the permutation — Observation 1 in the paper shows no worst-case guarantee
exists, which our kappa-hat benchmark reproduces empirically.

Two equivalent formulations live here:

* the **gather form** (:func:`bucketing`): permute, reshape, mean — the
  leaf-streamed XLA path;
* the **matrix form** (:func:`bucket_matrix`): a (ceil(n/s), n) sparse
  row-normalized assignment matrix B with ``B[b, i] = 1/|bucket b|`` iff
  worker i landed in bucket b, so the bucket means are the single MXU
  contraction ``B @ X``.  The fused Pallas bucketed-gram kernel
  (``repro.kernels.bucketgram``) streams exactly this contraction, which
  keeps the permutation a TRACED operand (one compile per fleet bucket
  regardless of the per-lane PRNG key).

Both share :func:`bucket_assignment` / :func:`bucket_counts`, so the
grouping (including the ragged tail bucket) can never drift between paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def default_bucket_size(n: int, f: int) -> int:
    """Paper / [26] choice: s = floor(n / 2f) (>= 1)."""
    if f <= 0:
        return 1
    return max(1, n // (2 * f))


def clamp_bucket_size(n: int, s: int | None, f) -> int:
    """Resolve + clamp a bucket size to [1, n] (shared by every path).

    The floor(n / 2f) default is shape material, so a traced f needs an
    explicit ``s``."""
    if s is None and not isinstance(f, int):
        raise ValueError(
            "a traced f needs an explicit bucket_size (the floor(n/2f) "
            "default is shape-level); set AggregatorSpec.bucket_size")
    s = s if s is not None else default_bucket_size(n, f)
    return max(1, min(int(s), n))


def num_buckets(n: int, s: int) -> int:
    """ceil(n / s)."""
    return -(-n // s)


def bucket_counts(n: int, s: int) -> Array:
    """True occupancy of each of the ceil(n/s) buckets, fp32.

    All buckets hold s workers except a possibly-ragged tail bucket
    (paper: n=17, s=2 -> 9 buckets, one singleton)."""
    n_buckets = num_buckets(n, s)
    return jnp.minimum(jnp.full((n_buckets,), s),
                       n - jnp.arange(n_buckets) * s).astype(jnp.float32)


def bucket_assignment(key: Array, n: int, s: int) -> Array:
    """(n,) int32 bucket id of every worker under the key's permutation.

    Worker i sits at position ``argsort(perm)[i]`` of the permuted stack
    ``x[perm]``, so its bucket is that position // s — byte-for-byte the
    grouping :func:`bucketing` produces with the same key."""
    perm = jax.random.permutation(key, n)
    inv = jnp.argsort(perm)
    return (inv // s).astype(jnp.int32)


def bucket_matrix(key: Array, n: int, s: int,
                  dtype: jnp.dtype = jnp.float32) -> Array:
    """Row-normalized (ceil(n/s), n) bucket-assignment matrix B.

    ``B @ X`` = the bucket means of ``X`` (ragged tail renormalized by true
    occupancy).  Built in-graph from the key so the permutation rides as a
    traced operand — the compiled kernel is key-independent."""
    n_buckets = num_buckets(n, s)
    assign = bucket_assignment(key, n, s)
    onehot = jax.nn.one_hot(assign, n_buckets, dtype=jnp.float32)  # (n, n_b)
    b = onehot.T / bucket_counts(n, s)[:, None]
    return b.astype(dtype)


def adjusted_f(f, n_buckets: int):
    """Downstream Byzantine budget after bucketing.

    Each Byzantine input contaminates at most one bucket, so f carries over
    unchanged — capped so the downstream rule still satisfies
    f' < n_buckets / 2 (exactly the paper's Observation 2 trade-off).  A
    python int stays one; a traced f (fleet lanes) gives a traced int32."""
    cap = max(0, (n_buckets - 1) // 2)
    if isinstance(f, int):
        return min(f, cap) if f else 0
    return jnp.minimum(jnp.asarray(f, jnp.int32), cap)


def bucketing(x: Array, f: int, key: Array, *, bucket_size: int | None = None
              ) -> tuple[Array, int]:
    """Returns (bucket means (ceil(n/s), d), adjusted f).

    Every bucket touched by >= 1 Byzantine input is arbitrarily manipulable,
    so the adjusted Byzantine count for the downstream rule stays f (each
    Byzantine input contaminates at most one bucket) while the population
    shrinks to ceil(n/s).

    Dtype-preserving: means accumulate in (at least) fp32 and are cast back
    to ``x.dtype``, matching every other rule's transport contract — a bf16
    stack no longer silently widens to fp32.
    """
    n = x.shape[0]
    s = clamp_bucket_size(n, bucket_size, f)
    acc_dtype = jnp.promote_types(x.dtype, jnp.float32)
    perm = jax.random.permutation(key, n)
    xp = x.astype(acc_dtype)[perm]
    n_buckets = num_buckets(n, s)
    pad = n_buckets * s - n
    if pad:
        # Ragged tail bucket: pad with zeros and renormalize by true count.
        xp = jnp.concatenate([xp, jnp.zeros((pad, x.shape[1]), acc_dtype)])
    counts = bucket_counts(n, s).astype(acc_dtype)
    sums = xp.reshape(n_buckets, s, -1).sum(axis=1)
    means = (sums / counts[:, None]).astype(x.dtype)
    return means, adjusted_f(f, n_buckets)


def bucketing_means(x: Array, f: int, key: Array, *, bucket_size: int | None = None
                    ) -> Array:
    return bucketing(x, f, key, bucket_size=bucket_size)[0]
