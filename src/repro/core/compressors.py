"""Traced compressor algebra: unbiased operators Q ∈ U(ω) (Definition 3)
and the biased top-k contraction, as *data* instead of Python callables.

A :class:`CompressorSpec` is a pytree (family id + parameters: dithering
level ``s``, kept fraction ``frac``, and a trailing :class:`SketchParams`
slot for the sketch families) whose fields may be **traced** jax values.
The three unified entry points

    compress(spec, key, x)   — apply Q
    spec_bits(spec, d)       — exact uplink payload bits of a d-element tensor
    spec_omega(spec, d)      — variance bound ω (Definition 3)

dispatch on the family id via ``lax.switch``, so a whole grid of compressor
choices (levels, fractions, sketch widths, even families) becomes a
vmappable axis: one compiled program sweeps every point (see
``repro.core.flecs``'s ``make_flecs_sweep_step`` / ``driver.run_sweep``).
Under the grid's ``vmap`` the family id is batched, and a switch over a
batched index runs every branch and selects.  So a spec also carries, as
static pytree structure, the set of family ids its ``family`` leaf can
hold (``CompressorSpec.families``), and ``compress`` and ``spec_omega``
switch over that set only: a level-only grid calls its one branch
directly, a two-family axis selects between two, and only a grid that
holds all six runs all six.  ``spec_bits`` keeps the full switch (its
docstring says why).
``compress`` and ``spec_bits`` take a static ``use_kernel`` flag that swaps
the dither and top-k branch bodies for the fused Pallas kernels
(``repro.kernels.compressor`` — bit-identical, interpret mode off-TPU);
the jnp expressions below stay the differential reference.  The static
:class:`Compressor` wrapper is a thin veneer over the same spec machinery,
so the static and sweep paths are trace-identical by construction — same
ops, same key consumption.

Construction: :func:`make_spec` is THE entry point.  It accepts a registry
name (``"identity"``, ``"dither64"``, ``"natural"``, ``"topk0.1"``,
``"count_sketch64"``, ``"minmax0.25"`` — the numeric suffix is the family's
main parameter), an existing :class:`CompressorSpec`, or a
:class:`Compressor`, plus per-family keyword parameters (``s``, ``frac``,
``width``/``depth``/``hh_frac``).  Unknown names and mis-parameterized
calls fail loudly with the valid-name list instead of surfacing as an
opaque switch-index error at trace time.  The historical trio
``spec_from_name`` / ``as_spec`` / ``get_compressor`` remain as thin
DEPRECATED aliases of ``make_spec``.

Wire-format accounting — THE pricing contract: ``spec_bits(spec, d)``
(and its veneer ``Compressor.bits(d)``) is the single wire-price query,
reporting the exact payload a real federation would ship for a d-element
tensor — reproducing the paper's communicated-bits x-axis.  Prices are
dimension-aware (top-k/min-max pay per kept value plus a ⌈log2 d⌉-bit
index; a count sketch pays for its ``depth·width`` accumulator regardless
of d), which is why the per-value query ``Compressor.bits_per_value`` is
DEPRECATED: it only ever made sense for the families whose price is
linear in d and raises for the rest.  Every ledger and ``round_bits``
price in the repo derives from ``spec_bits``.

The six families:

* identity — Q(x) = x; 32·d bits; ω = 0.
* dither — random ∞-norm dithering (the paper's experimental choice,
  s levels, p = ∞): Q(x) = ||x||_inf · sign(x) · xi(|x|/||x||_inf) where
  xi stochastically rounds to the grid {0, 1/s, ..., 1}.  Unbiased with
  ω = d/(4s²) (property-tested).
* natural — exponent-only stochastic rounding; 9·d bits; ω = 1/8.
* topk — biased contraction keeping the ⌈frac·d⌉ largest magnitudes.
* count_sketch — CSVec-style LINEAR sketch: hash the d coordinates into a
  ``[depth, width]`` sign-hashed accumulator (see
  :func:`count_sketch_encode`), unsketch via the per-row median estimate
  with top-k heavy-hitter extraction (``hh_frac``;
  :func:`count_sketch_decode`).  Unbiased at ``hh_frac = 1`` with
  ω = d/width per estimator row (collision variance; the heavy-hitter
  truncation below 1 adds a top-k-style contraction bias on top).
  Because the ENCODE is linear — sketch(Σx) == Σ sketch(x) for a shared
  hash key — aggregation commutes with compression: partial sums may be
  added in sketch domain and decoded once (``spec_commutes_with_sum``;
  the ``core.hierarchy`` edge fast path).  32·depth·width wire bits,
  independent of d (width is clipped to d).
* minmax — unbiased min-max / iceberg sampling: coordinate i survives
  with probability p_i = min(1, k·|x_i|/||x||₁), k = ⌈frac·d⌉, and is
  inverse-probability reweighted (x_i/p_i) so E Q(x) = x exactly.
  ⌈frac·d⌉·(32 + ⌈log2 d⌉) bits; ω ≤ d/k (from Σ x_i²/p_i ≤ ||x||₁²/k
  and Cauchy–Schwarz).

``encode_int8``/``decode_int8``/``shared_scale_levels`` give the integer
wire format used by the TPU-pod compressed all-reduce.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels.compressor import ops as kernel_ops
from repro.numerics import ceil_log2

# Family ids — the branch index of every spec-dispatched op's table.
FAMILY_IDENTITY = 0
FAMILY_DITHER = 1
FAMILY_NATURAL = 2
FAMILY_TOPK = 3
FAMILY_COUNT_SKETCH = 4
FAMILY_MINMAX = 5

#: Every family id, in branch order: the family set of a spec whose
#: ``family`` leaf the code cannot see.
ALL_FAMILIES = (FAMILY_IDENTITY, FAMILY_DITHER, FAMILY_NATURAL, FAMILY_TOPK,
                FAMILY_COUNT_SKETCH, FAMILY_MINMAX)

#: ``jax.named_scope`` of each family's implementation, by family id: the
#: compiled instructions of a branch carry ``compress.<family>`` in their
#: ``op_name``, so a trace tells the branches apart even where a batched
#: family id runs every branch of the spec's family set.  Identity has no
#: operations to name.
COMPRESS_SCOPES = ("compress.identity", "compress.dither",
                   "compress.natural", "compress.topk",
                   "compress.count_sketch", "compress.minmax")

#: Static row capacity of the count-sketch accumulator.  ``depth`` is a
#: TRACED parameter clipped to [1, SKETCH_DEPTH_MAX]; the accumulator is
#: allocated at the static maximum so depth can ride a sweep axis without
#: changing any shape.
SKETCH_DEPTH_MAX = 7

DEFAULT_SKETCH_WIDTH = 64.0
DEFAULT_SKETCH_DEPTH = 3.0


class SketchParams(NamedTuple):
    """Traced count-sketch parameters (ignored by the other families).

    width:   accumulator columns (clipped to [1, d] at apply time).
    depth:   estimator rows (clipped to [1, SKETCH_DEPTH_MAX]).
    hh_frac: heavy-hitter kept fraction in (0, 1] applied to the median
             estimate on decode (1 keeps every coordinate — the unbiased
             setting).
    """
    width: jnp.ndarray
    depth: jnp.ndarray
    hh_frac: jnp.ndarray


def default_sketch_params(shape=()) -> SketchParams:
    full = lambda v: jnp.full(shape, v, jnp.float32)      # noqa: E731
    return SketchParams(full(DEFAULT_SKETCH_WIDTH),
                        full(DEFAULT_SKETCH_DEPTH), full(1.0))


class CompressorSpec(NamedTuple):
    """Traced compressor description: (family, s, frac, params) as jnp
    scalars — or [G] arrays across a sweep-grid axis.

    family: int32 branch id (FAMILY_*).
    s:      float32 dithering level count (FAMILY_DITHER; ignored elsewhere).
    frac:   float32 kept fraction in (0, 1] (FAMILY_TOPK / FAMILY_MINMAX;
            ignored elsewhere).
    params: :class:`SketchParams` for FAMILY_COUNT_SKETCH (ignored
            elsewhere).  Trailing and defaulted (R5): legacy 3-field
            construction still works and is normalized by
            :func:`fill_params` at every entry point.
    families: the sorted FAMILY_* ids ``family`` can hold.  Static: it
            is the pytree node's aux data, not a leaf, so it lives
            through ``jit`` arguments, ``vmap``, ``jax.tree.map`` and
            ``shard_map`` specs, and the dispatch sees it while tracing.
            The constructors set it, :func:`stack_specs` unions it, and
            ``None`` (bare construction) means all six.  ``_replace`` of
            ``family`` without ``families`` resets it to all six: a set
            that leaves out an id the leaf holds would clamp to a wrong
            branch.  Specs with different sets are different pytree
            structures, so stack them with :func:`stack_specs`, not a
            ``jax.tree.map`` over the specs.
    """
    family: jnp.ndarray
    s: jnp.ndarray
    frac: jnp.ndarray
    params: Optional[SketchParams] = None
    families: Optional[Tuple[int, ...]] = None


def family_set(spec: CompressorSpec) -> Tuple[int, ...]:
    """The family ids ``spec.family`` can hold (all six when unknown)."""
    return ALL_FAMILIES if spec.families is None else spec.families


_SPEC_KEYS = tuple(jax.tree_util.GetAttrKey(f)
                   for f in CompressorSpec._fields[:4])
_namedtuple_replace = CompressorSpec._replace


def _spec_replace(self, **fields):
    if "family" in fields and "families" not in fields:
        fields["families"] = None
    return _namedtuple_replace(self, **fields)


# typing.NamedTuple refuses a ``_replace`` in the class body
CompressorSpec._replace = _spec_replace
jax.tree_util.register_pytree_with_keys(
    CompressorSpec,
    lambda spec: (tuple(zip(_SPEC_KEYS, spec[:4])), family_set(spec)),
    lambda families, leaves: CompressorSpec(*leaves, families=families),
    lambda spec: (spec[:4], family_set(spec)))


def fill_params(spec: CompressorSpec) -> CompressorSpec:
    """Normalize a legacy 3-slot spec (``params=None``) to the full 4-slot
    layout, broadcasting default sketch params to the spec's grid shape —
    so every spec-dispatched op sees one pytree structure and stacked
    family axes mix sketch and non-sketch points freely."""
    if spec.params is not None:
        return spec
    return spec._replace(params=default_sketch_params(jnp.shape(spec.family)))


def identity_spec() -> CompressorSpec:
    return CompressorSpec(jnp.int32(FAMILY_IDENTITY), jnp.float32(1.0),
                          jnp.float32(1.0), default_sketch_params(),
                          (FAMILY_IDENTITY,))


def dither_spec(s) -> CompressorSpec:
    """Random ∞-norm dithering with a possibly *traced* level count s.
    A [G] array of levels yields a [G] spec (a sweep-grid axis)."""
    s = jnp.asarray(s, jnp.float32)
    return CompressorSpec(jnp.full(s.shape, FAMILY_DITHER, jnp.int32), s,
                          jnp.ones(s.shape, jnp.float32),
                          default_sketch_params(s.shape), (FAMILY_DITHER,))


def natural_spec() -> CompressorSpec:
    return CompressorSpec(jnp.int32(FAMILY_NATURAL), jnp.float32(1.0),
                          jnp.float32(1.0), default_sketch_params(),
                          (FAMILY_NATURAL,))


def topk_spec(frac) -> CompressorSpec:
    """Biased top-k contraction keeping a possibly *traced* fraction.
    A [G] array of fractions yields a [G] spec (a sweep-grid axis)."""
    frac = jnp.asarray(frac, jnp.float32)
    return CompressorSpec(jnp.full(frac.shape, FAMILY_TOPK, jnp.int32),
                          jnp.ones(frac.shape, jnp.float32), frac,
                          default_sketch_params(frac.shape), (FAMILY_TOPK,))


def count_sketch_spec(width=DEFAULT_SKETCH_WIDTH, depth=DEFAULT_SKETCH_DEPTH,
                      hh_frac=1.0) -> CompressorSpec:
    """CSVec-style linear count sketch with possibly *traced* width /
    depth / heavy-hitter fraction.  A [G] array of widths yields a [G]
    spec (a sweep-grid axis); scalar depth/hh_frac broadcast to it."""
    width = jnp.asarray(width, jnp.float32)
    bcast = lambda v: jnp.broadcast_to(                   # noqa: E731
        jnp.asarray(v, jnp.float32), width.shape)
    return CompressorSpec(
        jnp.full(width.shape, FAMILY_COUNT_SKETCH, jnp.int32),
        jnp.ones(width.shape, jnp.float32), jnp.ones(width.shape, jnp.float32),
        SketchParams(width, bcast(depth), bcast(hh_frac)),
        (FAMILY_COUNT_SKETCH,))


def minmax_spec(frac) -> CompressorSpec:
    """Unbiased min-max (iceberg) sampling keeping ~⌈frac·d⌉ coordinates
    with probability proportional to magnitude, inverse-probability
    reweighted.  A [G] array of fractions yields a [G] spec."""
    frac = jnp.asarray(frac, jnp.float32)
    return CompressorSpec(jnp.full(frac.shape, FAMILY_MINMAX, jnp.int32),
                          jnp.ones(frac.shape, jnp.float32), frac,
                          default_sketch_params(frac.shape), (FAMILY_MINMAX,))


# ---------------------------------------------------------------------------
# Construction — make_spec is THE entry point; the old trio are aliases
# ---------------------------------------------------------------------------

_VALID_NAMES = ("'identity'", "'dither<s>' (e.g. 'dither64')", "'natural'",
                "'topk<frac>' (e.g. 'topk0.1')",
                "'count_sketch<width>' (e.g. 'count_sketch64')",
                "'minmax<frac>' (e.g. 'minmax0.25')")


def _unknown_name(name: str) -> ValueError:
    return ValueError(
        f"unknown compressor name {name!r}; valid names: "
        + ", ".join(_VALID_NAMES)
        + " — numeric suffixes may instead be passed as make_spec keywords")


def make_spec(name_or_spec: Union[str, CompressorSpec, "Compressor"],
              **params) -> CompressorSpec:
    """THE compressor constructor — parse a registry name (or pass through
    an existing spec) into a params-normalized :class:`CompressorSpec`.

    Accepted forms:

    * ``make_spec("dither64")`` — name with the family's main parameter as
      a numeric suffix (``dither<s>``, ``topk<frac>``,
      ``count_sketch<width>``, ``minmax<frac>``; ``identity`` / ``natural``
      take none).
    * ``make_spec("count_sketch", width=128, depth=5, hh_frac=0.5)`` —
      bare family name with keyword parameters (per family: dither ``s``;
      topk/minmax ``frac``; count_sketch ``width``/``depth``/``hh_frac``).
      Giving the same parameter in both the suffix and a keyword is an
      error (no silent override), as is any keyword the family does not
      take.
    * ``make_spec(spec)`` / ``make_spec(compressor)`` — pass-through
      (normalized via :func:`fill_params`); keywords are rejected, a spec
      is immutable data.

    Unknown names raise ``ValueError`` listing every valid name — at
    construction time, not as an opaque switch-index error deep inside a
    trace.
    """
    if isinstance(name_or_spec, CompressorSpec):
        if params:
            raise ValueError(
                "make_spec(spec, **params): keyword parameters only apply "
                "to name-based construction; rebuild the spec instead")
        return fill_params(name_or_spec)
    if isinstance(name_or_spec, Compressor):
        if params:
            raise ValueError(
                "make_spec(compressor, **params): keyword parameters only "
                "apply to name-based construction")
        return fill_params(name_or_spec.spec)
    if not isinstance(name_or_spec, str):
        raise TypeError(
            f"make_spec takes a name, CompressorSpec, or Compressor — got "
            f"{type(name_or_spec).__name__}")
    name = name_or_spec

    def suffix_param(prefix, cast, pname):
        raw = name[len(prefix):]
        if not raw:
            return
        if pname in params:
            raise ValueError(
                f"compressor parameter {pname!r} given both in the name "
                f"{name!r} and as a keyword — pick one")
        try:
            params[pname] = cast(raw)
        except ValueError:
            raise _unknown_name(name) from None

    if name == "identity":
        allowed, ctor = (), identity_spec
    elif name == "natural":
        allowed, ctor = (), natural_spec
    elif name.startswith("count_sketch"):
        allowed = ("width", "depth", "hh_frac")
        suffix_param("count_sketch", int, "width")
        ctor = lambda: count_sketch_spec(**params)        # noqa: E731
    elif name.startswith("dither"):
        allowed = ("s",)
        suffix_param("dither", int, "s")
        ctor = lambda: dither_spec(params.get("s", 64))   # noqa: E731
    elif name.startswith("minmax"):
        allowed = ("frac",)
        suffix_param("minmax", float, "frac")
        ctor = lambda: minmax_spec(params.get("frac", 0.1))   # noqa: E731
    elif name.startswith("topk"):
        allowed = ("frac",)
        suffix_param("topk", float, "frac")
        ctor = lambda: topk_spec(params.get("frac", 0.1))     # noqa: E731
    else:
        raise _unknown_name(name)
    unknown = set(params) - set(allowed)
    if unknown:
        raise ValueError(
            f"unknown parameter(s) {sorted(unknown)} for compressor "
            f"{name!r}; this family takes {list(allowed) or 'no parameters'}")
    return ctor()


def _warn_deprecated(old: str, repl: str) -> None:
    warnings.warn(f"compressors.{old} is deprecated; use {repl}",
                  DeprecationWarning, stacklevel=3)


def spec_from_name(name: str) -> CompressorSpec:
    """DEPRECATED alias of :func:`make_spec` (name form)."""
    _warn_deprecated("spec_from_name(name)", "make_spec(name)")
    return make_spec(name)


# ---------------------------------------------------------------------------
# Family implementations (each also usable standalone with traced params)
# ---------------------------------------------------------------------------

def _dither(key, x, s):
    xf = x.astype(jnp.float32)
    norm = jnp.max(jnp.abs(xf))
    norm = jnp.where(norm == 0, 1.0, norm)
    y = jnp.abs(xf) / norm * s                   # in [0, s]
    lo = jnp.floor(y)
    p = y - lo                                   # P(round up)
    u = jax.random.uniform(key, x.shape)
    level = lo + (u < p)
    out = jnp.sign(xf) * level * norm / s
    return out.astype(x.dtype)


def dither(key, x, s):
    """Random dithering with a possibly *traced* level count s — what lets
    ``jax.vmap`` sweep compressor levels inside one compiled program."""
    return _dither(key, x, s)


def dither_bits(s):
    """Wire bits/value of s-level dithering, ceil(log2(2s+1)); traced-safe."""
    return ceil_log2(2.0 * s + 1.0)


@jax.named_scope(COMPRESS_SCOPES[FAMILY_NATURAL])
def _natural(key, x):
    """Natural compression [13]: keep the exponent, round the mantissa to a
    power of two stochastically.  Unbiased with ω = 1/8 (tight at p = 1/3)."""
    xf = x.astype(jnp.float32)
    ax = jnp.abs(xf)
    lo = jnp.where(ax > 0, 2.0 ** jnp.floor(jnp.log2(jnp.maximum(ax, 1e-38))),
                   0.0)
    p = jnp.where(lo > 0, (ax - lo) / lo, 0.0)   # in [0,1): round to 2*lo w.p p
    u = jax.random.uniform(key, x.shape)
    mag = jnp.where(u < p, 2.0 * lo, lo)
    return (jnp.sign(xf) * mag).astype(x.dtype)


def _topk(key, x, frac):
    """Top-k with a possibly *traced* fraction, via the k-th-largest
    magnitude threshold: keep everything strictly above it plus the
    lowest-index ties up to k = ceil(frac·d) — exactly ``lax.top_k``'s
    selection (ties prefer the lower index), but k may be traced, and one
    value-only sort is ~2x faster than argsort + scatter."""
    del key
    flat = x.reshape(-1)
    d = flat.shape[0]
    k = jnp.clip(jnp.ceil(frac * d).astype(jnp.int32), 1, d)
    ax = jnp.abs(flat)
    thresh = jnp.sort(ax)[d - k]                 # k-th largest magnitude
    above = ax > thresh
    n_above = jnp.sum(above.astype(jnp.int32))
    ties = ax == thresh
    tie_rank = jnp.cumsum(ties.astype(jnp.int32))          # 1-based
    keep = above | (ties & (tie_rank <= k - n_above))
    out = jnp.where(keep, flat, jnp.zeros((), flat.dtype))
    return out.reshape(x.shape)


def _sketch_hashes(key, d: int, width):
    """Bucket/sign hash tables ``[SKETCH_DEPTH_MAX, d]`` derived from
    ``key`` alone — so encode and decode (and every worker sharing the
    round key) agree on the hash functions, which is what makes the
    sketch LINEAR across messages.  ``width`` may be traced: buckets are
    drawn uniform over int32 and reduced modulo the clipped width (the
    modulo bias is O(width/2³¹) and irrelevant to unbiasedness, which
    only needs the signs to be independent ±1)."""
    kb, ks = jax.random.split(key)
    wc = jnp.clip(jnp.floor(jnp.asarray(width, jnp.float32)).astype(
        jnp.int32), 1, d)
    raw = jax.random.randint(kb, (SKETCH_DEPTH_MAX, d), 0,
                             jnp.iinfo(jnp.int32).max)
    bucket = raw % wc
    sign = jax.random.rademacher(ks, (SKETCH_DEPTH_MAX, d), jnp.float32)
    return bucket, sign


def count_sketch_encode(key, x, params: SketchParams):
    """Sketch x into the ``[SKETCH_DEPTH_MAX, d]`` sign-hashed accumulator
    (rows past the traced depth are computed but ignored by decode and
    priced at zero; columns past the clipped width stay zero).  LINEAR in
    x for a fixed key: encode(key, x + y) == encode(key, x) + encode(key, y)
    up to f32 reassociation — the property the hierarchy's sketch-domain
    aggregation fast path rests on."""
    flat = x.reshape(-1).astype(jnp.float32)
    d = flat.shape[0]
    bucket, sign = _sketch_hashes(key, d, params.width)
    rows = jnp.arange(SKETCH_DEPTH_MAX)[:, None]
    table = jnp.zeros((SKETCH_DEPTH_MAX, d), jnp.float32)
    return table.at[rows, bucket].add(sign * flat[None, :])


def count_sketch_decode(key, table, x_like, params: SketchParams):
    """Unsketch: per-row estimate sign·table[row, bucket], masked median
    over the traced depth's active rows (each row's collision noise is
    symmetric about the true value, so the median is exactly unbiased),
    then top-k heavy-hitter extraction at ``hh_frac`` (1 keeps all)."""
    d = table.shape[1]
    bucket, sign = _sketch_hashes(key, d, params.width)
    est = sign * jnp.take_along_axis(table, bucket, axis=1)
    dep = jnp.clip(jnp.floor(jnp.asarray(params.depth, jnp.float32)).astype(
        jnp.int32), 1, SKETCH_DEPTH_MAX)
    active = jnp.arange(SKETCH_DEPTH_MAX)[:, None] < dep
    srt = jnp.sort(jnp.where(active, est, jnp.inf), axis=0)
    lo = jnp.take_along_axis(srt, jnp.broadcast_to((dep - 1) // 2, (1, d)),
                             axis=0)[0]
    hi = jnp.take_along_axis(srt, jnp.broadcast_to(dep // 2, (1, d)),
                             axis=0)[0]
    med = 0.5 * (lo + hi)
    out = _topk(None, med, params.hh_frac)
    return out.reshape(x_like.shape).astype(x_like.dtype)


@jax.named_scope(COMPRESS_SCOPES[FAMILY_COUNT_SKETCH])
def _count_sketch(key, x, params: SketchParams):
    """Q(x) = decode(encode(x)) — the flat (single-message) sketch path."""
    return count_sketch_decode(key, count_sketch_encode(key, x, params),
                               x, params)


@jax.named_scope(COMPRESS_SCOPES[FAMILY_MINMAX])
def _minmax(key, x, frac):
    """Min-max / iceberg sampling: coordinate i survives with probability
    p_i = min(1, k·|x_i|/||x||₁) and ships x_i/p_i — exactly unbiased
    (E keep_i·x_i/p_i = x_i; p_i = 0 only where x_i = 0).  E[#kept] ≤ k."""
    flat = x.reshape(-1).astype(jnp.float32)
    d = flat.shape[0]
    k = jnp.clip(jnp.ceil(frac * d), 1.0, d)
    ax = jnp.abs(flat)
    l1 = jnp.sum(ax)
    p = jnp.clip(k * ax / jnp.maximum(l1, 1e-30), 0.0, 1.0)
    u = jax.random.uniform(key, flat.shape)
    out = jnp.where(u < p, flat / jnp.maximum(p, 1e-30), 0.0)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# Fused-kernel dispatch (repro.kernels.compressor)
# ---------------------------------------------------------------------------

@jax.named_scope(COMPRESS_SCOPES[FAMILY_DITHER])
def _dither_impl(key, x, s, use_kernel):
    """Dither branch body: the fused Pallas kernel when requested and
    statically eligible (``ops.supports``), else the jnp reference."""
    if use_kernel and kernel_ops.supports(x):
        return kernel_ops.fused_dither(key, x, s)[0]
    return _dither(key, x, s)


@jax.named_scope(COMPRESS_SCOPES[FAMILY_TOPK])
def _topk_impl(key, x, frac, use_kernel):
    """Top-k branch body: fused kernel when eligible, else jnp."""
    if use_kernel and kernel_ops.supports(x):
        return kernel_ops.fused_topk(key, x, frac)[0]
    return _topk(key, x, frac)


def _dither_bits_impl(s, d, use_kernel):
    """Dither ledger branch: the bits-only kernel shares its formula
    with the fused kernel's in-pass count, so both prices agree."""
    if use_kernel:
        return kernel_ops.dither_bits_fused(s, d)
    return dither_bits(s) * d


def _topk_bits_impl(frac, d, kept, use_kernel):
    """Top-k ledger branch (``kept`` precomputed by the caller so the
    jnp expression stays identical to the pre-kernel code)."""
    if use_kernel:
        return kernel_ops.topk_bits_fused(frac, d)
    return kept * (32.0 + ceil_log2(jnp.maximum(d, 1.0)))


# ---------------------------------------------------------------------------
# Unified spec-dispatched ops (lax.switch over the family id)
# ---------------------------------------------------------------------------

def _family_switch(spec: CompressorSpec, branches):
    """``lax.switch(spec.family, branches)`` over the families ``spec`` can
    hold only.  ``branches`` is the full table, one thunk per FAMILY_* id.

    Under a batched family id (the sweep grid's ``vmap``) a switch runs
    every branch and selects, so a branch the set leaves out is work never
    done; the branches it keeps compute what they computed in the full
    switch.  The id indexes by its place in the sorted set, which is the
    id itself (clamped alike) when the set holds all six, and a one-branch
    switch calls its branch directly."""
    families = family_set(spec)
    place = jnp.searchsorted(jnp.asarray(families, jnp.int32), spec.family,
                             method="compare_all")
    return jax.lax.switch(place, [branches[f] for f in families])


def compress(spec: CompressorSpec, key, x, use_kernel: bool = False
             ) -> jnp.ndarray:
    """Q(x) under ``spec`` — every field may be traced, so the compressor
    choice itself is a vmappable sweep axis.

    ``use_kernel=True`` (a STATIC flag) routes the dither and top-k
    families through the fused Pallas kernels
    (``repro.kernels.compressor``, interpret mode off-TPU) when the
    tensor is eligible; identity/natural and the sketch families — and
    tensors past the kernels' documented limit (``ops.supports``) — keep
    the jnp path.  The kernels are bit-identical to the jnp reference under a
    consistent evaluation context (the differential suite in
    tests/test_kernels.py pins it), so the two paths are interchangeable
    mid-run."""
    spec = fill_params(spec)
    return _family_switch(
        spec,
        (lambda: x,
         lambda: _dither_impl(key, x, spec.s, use_kernel),
         lambda: _natural(key, x),
         lambda: _topk_impl(key, x, spec.frac, use_kernel),
         lambda: _count_sketch(key, x, spec.params),
         lambda: _minmax(key, x, spec.frac)))


def spec_bits(spec: CompressorSpec, d, use_kernel: bool = False
              ) -> jnp.ndarray:
    """Exact uplink payload bits of compressing a d-element tensor — THE
    wire-price query every ledger and ``round_bits`` derives from.

    identity:     32·d.
    dither:       ⌈log2(2s+1)⌉·d (sign+level; the shared norm is 32 bits,
                  amortized as in the paper's accounting).
    natural:      9·d (sign + 8-bit exponent).
    top-k:        ⌈frac·d⌉ kept values, each shipping a 32-bit payload plus
                  a ⌈log2 d⌉-bit index — dimension-aware, unlike the old
                  flat 64·frac per element which hardcoded a 32-bit index.
    count_sketch: 32·⌊depth⌋·⌊width⌋ accumulator counters (width clipped
                  to d) — independent of d, the whole point of sketching.
    minmax:       ⌈frac·d⌉ provisioned value+index slots, priced like
                  top-k (E[#kept] ≤ ⌈frac·d⌉; slots are reserved, not
                  data-dependent, so the ledger stays deterministic).

    ``use_kernel=True`` prices the dither/top-k branches through the
    bits-only ledger kernels, which share their formulas with the fused
    value kernels' in-pass counts — EXACTLY the numbers above.

    The price keeps the full six-branch switch, whatever the spec's
    family set: its branches are a few scalar operations a point, so
    narrowing them saves nothing, and for a static spec a price that
    folds to a constant early in XLA:CPU's pipeline changes how a
    thinned scan's loop-invariant data is compiled, so thinned and dense
    traces stop matching bit for bit.
    """
    spec = fill_params(spec)
    d = jnp.asarray(d, jnp.float32)
    kept = jnp.clip(jnp.ceil(spec.frac * d), 1.0, d)
    idx_bits = 32.0 + ceil_log2(jnp.maximum(d, 1.0))
    dep = jnp.clip(jnp.floor(spec.params.depth), 1.0,
                   float(SKETCH_DEPTH_MAX))
    wc = jnp.clip(jnp.floor(spec.params.width), 1.0, d)
    return jax.lax.switch(
        spec.family,
        (lambda: 32.0 * d,
         lambda: _dither_bits_impl(spec.s, d, use_kernel),
         lambda: 9.0 * d,
         lambda: _topk_bits_impl(spec.frac, d, kept, use_kernel),
         lambda: 32.0 * dep * wc,
         lambda: kept * idx_bits))


def spec_bits_many(spec: CompressorSpec, d) -> jnp.ndarray:
    """:func:`spec_bits` for a STACKED spec whose leaves carry a leading
    [G] grid axis — the per-point wire-price query behind plan-level bit
    budgets (``lax.switch`` needs a scalar family id, so a grid-stacked
    spec is vmapped over its axis).  Scalar specs pass straight through,
    so callers can price any hparam pytree uniformly."""
    if jnp.ndim(spec.family) == 0:
        return spec_bits(spec, d)
    return jax.vmap(lambda s: spec_bits(s, d))(spec)


def spec_omega(spec: CompressorSpec, d) -> jnp.ndarray:
    """Variance bound ω of Definition 3 (0 for identity; top-k is a biased
    contraction, not in U(ω) — reported as 0 and flagged by ``unbiased``).
    count_sketch: d/width per-row collision variance (valid at
    hh_frac = 1; heavy-hitter truncation below 1 adds top-k-style bias).
    minmax: d/⌈frac·d⌉ (Σ x_i²/p_i ≤ ||x||₁²/k ≤ (d/k)·||x||², tested)."""
    spec = fill_params(spec)
    d = jnp.asarray(d, jnp.float32)
    kept = jnp.clip(jnp.ceil(spec.frac * d), 1.0, d)
    wc = jnp.clip(jnp.floor(spec.params.width), 1.0, d)
    return _family_switch(
        spec,
        (lambda: jnp.float32(0.0),
         lambda: d / (4.0 * spec.s * spec.s),
         lambda: jnp.float32(1.0 / 8.0),
         lambda: jnp.float32(0.0),
         lambda: d / wc,
         lambda: d / kept))


def spec_commutes_with_sum(spec: CompressorSpec) -> jnp.ndarray:
    """Traced predicate: may partial sums be aggregated in the compressed
    domain, i.e. is the ENCODING a linear map?

    Hierarchical aggregation (``repro.core.hierarchy``) and psum-style
    sharded reductions only reproduce the flat server algebra when the
    compressor commutes with summation.  That is the identity family and
    the count-sketch family: sketch(Σxᵢ) == Σ sketch(xᵢ) for a shared
    hash key, so an edge tier may sum sketches and decode ONCE at the
    root — the estimator of the summed message, exactly what flat
    compression of the sum would produce (up to f32 reassociation).
    Random dithering and natural compression are UNBIASED but not linear
    (stochastic rounding of a sum is not the sum of roundings), and top-k
    / min-max sampling are data-dependent selections — re-aggregating
    their outputs changes the estimator, which is the trade-off an
    edge-compression sweep measures rather than a bug.
    """
    return ((spec.family == FAMILY_IDENTITY)
            | (spec.family == FAMILY_COUNT_SKETCH))


# ---------------------------------------------------------------------------
# Static wrapper (the thin registry veneer over the spec algebra)
# ---------------------------------------------------------------------------

#: Families whose wire price is NOT linear in d — a per-value price query
#: is meaningless for them (see ``Compressor.bits_per_value``).
_DIM_DEPENDENT_FAMILIES = (FAMILY_TOPK, FAMILY_COUNT_SKETCH, FAMILY_MINMAX)


@dataclasses.dataclass(frozen=True)
class Compressor:
    """A named static spec.  ``compress``/``bits``/``omega`` all route
    through the traced algebra, so static and sweep paths are op-identical."""
    name: str
    spec: CompressorSpec
    unbiased: bool = True

    def compress(self, key, x):
        return compress(self.spec, key, x)

    def bits(self, d) -> float:
        """Total payload bits for a d-element tensor (dimension-aware) —
        THE price query; see the module docstring's pricing contract."""
        return float(spec_bits(self.spec, d))

    @property
    def bits_per_value(self) -> float:
        """DEPRECATED per-element price — only ever defined for the
        families whose wire size is linear in d (identity/dither/natural);
        raises for the rest.  Use ``.bits(d)``, the single price query."""
        warnings.warn(
            "Compressor.bits_per_value is deprecated; .bits(d) is the "
            "single wire-price query (see the compressors module "
            "docstring)", DeprecationWarning, stacklevel=2)
        if int(self.spec.family) in _DIM_DEPENDENT_FAMILIES:
            raise ValueError(
                f"{self.name}: wire size is dimension-dependent "
                "(top-k/min-max pay (32 + ceil(log2 d)) bits per kept "
                "value; a count sketch pays its depth*width accumulator); "
                "use .bits(d)")
        return float(spec_bits(self.spec, 1))

    def omega(self, d: int) -> float:
        return float(spec_omega(self.spec, d))


def identity() -> Compressor:
    return Compressor("identity", identity_spec())


def random_dithering(s: int = 64) -> Compressor:
    """∞-norm random dithering with s levels; ω = d/(4s²)."""
    return Compressor(f"dither{s}", dither_spec(s))


def natural() -> Compressor:
    return Compressor("natural", natural_spec())


def top_k(frac: float = 0.1) -> Compressor:
    """Biased top-k contraction (used for the Hessian-sketch difference)."""
    return Compressor(f"topk{frac}", topk_spec(frac), unbiased=False)


def count_sketch(width: int = 64, depth: int = 3,
                 hh_frac: float = 1.0) -> Compressor:
    """Linear count sketch; unbiased at hh_frac = 1 (heavy-hitter
    truncation below 1 is a biased contraction, like top-k)."""
    return Compressor(f"count_sketch{width}",
                      count_sketch_spec(width, depth, hh_frac),
                      unbiased=hh_frac >= 1.0)


def min_max(frac: float = 0.1) -> Compressor:
    """Unbiased min-max / iceberg sampling at kept fraction ``frac``."""
    return Compressor(f"minmax{frac}", minmax_spec(frac))


def get_compressor(name: str) -> Compressor:
    """DEPRECATED alias: build a :class:`Compressor` from a registry name.
    Use :func:`make_spec` (specs are the uniform argument everywhere) or
    the explicit factories above."""
    _warn_deprecated("get_compressor(name)",
                     "make_spec(name) or the Compressor factories")
    return Compressor(name, make_spec(name),
                      unbiased=not name.startswith("topk"))


def as_spec(c: Union[str, CompressorSpec, Compressor]) -> CompressorSpec:
    """DEPRECATED alias of :func:`make_spec` (pass-through form)."""
    _warn_deprecated("as_spec(c)", "make_spec(c)")
    return make_spec(c)


def stack_specs(*specs: Union[str, CompressorSpec, Compressor]
                ) -> CompressorSpec:
    """Stack scalar specs into one [G] spec whose leading axis may vary the
    FAMILY itself — e.g. ``stack_specs("identity", "dither64")`` is the
    FLECS-vs-FLECS-CGD comparison as a single vmappable grid axis (the
    lax.switch dispatch keys on the traced family id per grid point).
    Inputs go through :func:`make_spec`, so names, specs, and Compressors
    mix freely and sketch params are normalized before stacking.  The
    stacked spec's family set is the union of its inputs' sets."""
    stacked = [make_spec(s) for s in specs]
    families = tuple(sorted(set().union(*map(family_set, stacked))))
    stacked = [s._replace(families=families) for s in stacked]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *stacked)


# ---------------------------------------------------------------------------
# int8 wire format for the compressed all-reduce (TPU-pod realization)
# ---------------------------------------------------------------------------

def encode_int8(key, x, s: int = 127):
    """Random dithering with s <= 127 levels, returning (int8 levels, scale).
    sum-compatible: decode(sum(levels)) == sum(decode(levels)) given scales."""
    xf = x.astype(jnp.float32)
    norm = jnp.max(jnp.abs(xf))
    norm = jnp.where(norm == 0, 1.0, norm)
    y = xf / norm * s                            # in [-s, s]
    lo = jnp.floor(y)
    u = jax.random.uniform(key, x.shape)
    level = lo + (u < (y - lo))
    return level.astype(jnp.int8), norm / s


def decode_int8(levels, scale):
    return levels.astype(jnp.float32) * scale


def psum_level_cap(s_levels, n_workers: int):
    """Dithering-level cap for the int8 collective, on the TRACED path.

    The f16 psum accumulation of ``n`` workers' integer levels is exact only
    while level sums stay <= 2047 (f16 holds integers exactly to 2048), so
    the usable level count is min(s, 2047 // n).  Expressed as a lax-side
    clip — not Python ``min`` — so ``s_levels`` can be a traced sweep axis
    (vmapping the DL trainer's wire format over level grids).  ``n_workers``
    is the static federation size (a mesh-axis product).
    """
    cap = jnp.float32(max(1, 2047 // n_workers))
    return jnp.clip(jnp.asarray(s_levels, jnp.float32), 1.0, cap)


def shared_scale_levels(key, x, s, axes):
    """int8 dithering levels with a pmax-shared scale — the collective
    realization of ``dither_spec(s)`` inside a shard_map: the scale is
    agreed across the mapped ``axes`` so the integer levels are
    sum-compatible under an integer/f16 psum (the compressed all-reduce
    of ``repro.core.dl_flecs``).  Returns (levels int8, scale f32)."""
    xf = x.astype(jnp.float32)
    norm = jax.lax.pmax(jnp.max(jnp.abs(xf)), axes)
    norm = jnp.where(norm == 0, 1.0, norm)
    y = xf / norm * s
    lo = jnp.floor(y)
    u = jax.random.uniform(key, x.shape)
    levels = (lo + (u < (y - lo))).astype(jnp.int8)
    return levels, norm / s
