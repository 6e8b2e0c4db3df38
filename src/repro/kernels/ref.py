"""Pure-jnp oracles for the width-generic SZx block-compression kernels.

These functions are the ground-truth semantics for the Pallas kernels in
``encode.py`` / ``unpack.py`` / ``planes.py`` / ``bitshuffle.py``.  Everything
here is fixed-shape (the variable-length byte compaction happens at the
host/serialization boundary in ``repro.core.codec.container``), which is what
makes the algorithm expressible on TPU.

Every transform op is parameterized by a :class:`repro.kernels.specs.DtypeSpec`
-- ONE implementation covers float32/float64/float16/bfloat16.  Per-block
statistics run in the spec's *compute dtype* (f32 for words up to 4 bytes,
f64 for float64; the 16-bit formats are exact subsets of f32), the bit-level
split runs on the *storage* word after rounding the normalized residual to the
input dtype.  With ``spec=specs.F32`` the results are bit-identical to the
original float32-only oracles.

Notation follows the paper (Algorithm 1 / Formulas 4-5):
  mu      -- mean of min and max of a block ("mean of min/max", mu_k)
  radius  -- variation radius r_k = max(|max-mu|, |mu-min|)
  reqlen  -- required number of leading IEEE-754 bits: 1 sign + exp_bits
             exponent + R_k mantissa bits, R_k = clip(p(r_k) - p(e) + 1, 0,
             mant_bits).  (+1 is a guard bit so the mu-subtraction rounding
             keeps the bound strict; see DESIGN.md section 2.)
  shift   -- Solution-C right shift s = (8 - reqlen % 8) % 8 (Formula 5)
  nbytes  -- bytes kept per value = (reqlen + shift) / 8; 0 marks a constant
             block.
  L       -- identical-leading-byte count vs. the predecessor (2-bit code,
             capped at min(3, itemsize)); predecessor of the first value in a
             block is the zero word (blocks are independently decodable, as in
             the GPU design).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import specs
from repro.kernels.specs import DtypeSpec

F32_EXP_BIAS = 127


def float_exponent(x, spec: DtypeSpec):
    """Biased-removed binary exponent field of |x| in the spec's COMPUTE dtype.

    floor(log2|x|) for compute-dtype normals; ``-compute_exp_bias`` for
    zero/subnormals (conservative: a too-large exponent keeps more bits).
    """
    c = jnp.asarray(x, spec.compute_np_dtype)
    bits = jax.lax.bitcast_convert_type(c, spec.compute_uint_dtype)
    field = (bits >> spec.compute_mant_bits) & ((1 << spec.compute_exp_bits) - 1)
    return field.astype(jnp.int32) - spec.compute_exp_bias


def f32_exponent(x):
    """Back-compat alias: exponent field of float32 |x|."""
    return float_exponent(x, specs.F32)


def block_stats_ref(xb: jax.Array, e, spec: DtypeSpec = specs.F32, p_e=None) -> tuple:
    """Per-block statistics (paper Alg. 1 lines 3-7), width-generic.

    xb: (nb, bs) in the spec's dtype (or castable).  e: scalar absolute error
    bound (> 0).  p_e: optional exact floor(log2 e) (int32 scalar); computed
    from the compute-dtype exponent field of e when absent.
    Returns (mu, radius, const, reqlen, shift, nbytes); mu is (nb,) in the
    spec's dtype, radius in the compute dtype, the rest int32/bool (nb,)-shaped
    with reqlen/shift/nbytes 0 for constant blocks.
    """
    cdt = spec.compute_np_dtype
    x = jnp.asarray(xb, spec.np_dtype).astype(cdt)
    e = jnp.asarray(e, cdt)
    mn = jnp.min(x, axis=-1)
    mx = jnp.max(x, axis=-1)
    mu = (0.5 * (mn + mx)).astype(spec.np_dtype)   # storage-rounded mu
    mu_w = mu.astype(cdt)                          # exact widening
    # radius vs the ROUNDED mu: the constant-block test then already covers
    # the mu storage rounding of the narrow dtypes
    radius = jnp.maximum(mx - mu_w, mu_w - mn)
    r_test = radius
    if spec.stats_rounding_guard:
        # 16-bit formats: the f32 subtraction can round BELOW the true block
        # deviation (<= 0.5 ulp); testing the next-up radius keeps the bound
        # strict (see DtypeSpec.stats_rounding_guard)
        bits = jax.lax.bitcast_convert_type(radius, spec.compute_uint_dtype) + 1
        r_test = jax.lax.bitcast_convert_type(bits, cdt)
    const = r_test <= e
    if p_e is None:
        p_e = float_exponent(e, spec)
    req_m_raw = float_exponent(radius, spec) - jnp.asarray(p_e, jnp.int32) + 1
    req_m = jnp.clip(req_m_raw, 0, spec.mant_bits)
    # Verbatim blocks (beyond-paper robustness): if the bound is below the
    # ulp of the normalized values (req_m_raw > mant_bits), the mu-subtraction
    # rounding alone can break the bound, so store the block bit-exactly by
    # normalizing against mu = 0.  Real SZx silently violates the bound here.
    mu = jnp.where(req_m_raw > spec.mant_bits, jnp.zeros_like(mu), mu)
    reqlen = 1 + spec.exp_bits + req_m      # 1 sign + exponent + R_k mantissa
    shift = (8 - reqlen % 8) % 8            # Formula (5), Solution C
    nbytes = (reqlen + shift) // 8
    zero = jnp.zeros_like(reqlen)
    return (
        mu,
        radius,
        const,
        jnp.where(const, zero, reqlen),
        jnp.where(const, zero, shift),
        jnp.where(const, zero, nbytes),
    )


def pack_ref(xb: jax.Array, mu: jax.Array, shift: jax.Array, nbytes: jax.Array,
             spec: DtypeSpec = specs.F32):
    """Normalize, right-shift (Solution C), XOR-lead, and byte-plane split.

    xb: (nb, bs) spec dtype; mu: (nb,) spec dtype; shift/nbytes: (nb,) int32.
    Returns:
      planes: (nb, itemsize, bs) uint8 -- byte j of the shifted word (0 = most
              significant).  Fixed shape; the serializer keeps only bytes with
              L <= j < nbytes.
      L:      (nb, bs) int32 -- identical leading bytes vs. predecessor,
              clipped to [0, min(lead_cap, nbytes)].
      mid:    (nb, bs) int32 -- mid-bytes to store per value (nbytes - L).
    """
    cdt = spec.compute_np_dtype
    udt = spec.uint_dtype
    x = jnp.asarray(xb, spec.np_dtype).astype(cdt)
    mu_w = jnp.asarray(mu, spec.np_dtype).astype(cdt)
    v = (x - mu_w[:, None]).astype(spec.np_dtype)  # storage-rounded residual
    w = jax.lax.bitcast_convert_type(v, udt)
    ws = w >> shift[:, None].astype(udt)
    prev = jnp.concatenate(
        [jnp.zeros((ws.shape[0], 1), udt), ws[:, :-1]], axis=1
    )
    xw = ws ^ prev
    # leading identical bytes vs predecessor (cumulative AND over MSB-first
    # byte equality), capped by the 2-bit code / word width at lead_cap
    L = jnp.zeros(ws.shape, jnp.int32)
    run = jnp.ones(ws.shape, bool)
    for j in range(spec.lead_cap):
        run = run & ((xw >> jnp.asarray(8 * (spec.itemsize - 1 - j), udt)) == 0)
        L = L + run.astype(jnp.int32)
    L = jnp.minimum(L, nbytes[:, None])
    planes = jnp.stack(
        [
            ((ws >> jnp.asarray(8 * (spec.itemsize - 1 - j), udt))
             & jnp.asarray(0xFF, udt)).astype(jnp.uint8)
            for j in range(spec.itemsize)
        ],
        axis=1,
    )
    mid = nbytes[:, None] - L
    return planes, L, mid


def encode_ref(xb: jax.Array, e, spec: DtypeSpec = specs.F32, p_e=None):
    """Fused block_stats + pack: one traced program, one device round trip.

    Returns (mu, const, reqlen, shift, nbytes, planes, L) -- exactly the
    fields the container layer serializes.  Bit-identical to calling
    :func:`block_stats_ref` then :func:`pack_ref`.
    """
    mu, _radius, const, reqlen, shift, nbytes = block_stats_ref(xb, e, spec, p_e)
    planes, L, _mid = pack_ref(xb, mu, shift, nbytes, spec)
    return mu, const, reqlen, shift, nbytes, planes, L


def _compose_word(ws, mu, shift, nbytes, spec: DtypeSpec):
    """Shift the reassembled word back, bitcast, and re-add mu (in the
    compute dtype, rounded to storage); constant blocks decode to mu."""
    w = ws << shift[:, None].astype(spec.uint_dtype)
    v = jax.lax.bitcast_convert_type(w, spec.np_dtype)
    mu_w = jnp.asarray(mu, spec.np_dtype).astype(spec.compute_np_dtype)
    x = (v.astype(spec.compute_np_dtype) + mu_w[:, None]).astype(spec.np_dtype)
    return jnp.where((nbytes == 0)[:, None], jnp.asarray(mu, spec.np_dtype)[:, None], x)


def unpack_ref(planes, mu, shift, nbytes, L, spec: DtypeSpec = specs.F32):
    """Inverse of pack_ref.

    Reconstructs each byte either from the stored plane entry or, for the L
    leading bytes, from the most recent predecessor that stored that plane --
    the paper's GPU "index propagation" realized as a cumulative max
    (associative scan) along the block.  Planes past the lead cap (L <= 3)
    are always stored for live blocks, so they skip the scan entirely.
    Returns (nb, bs) reconstruction in the spec's dtype (mu for constant
    blocks).
    """
    nb, _, bs = planes.shape
    udt = spec.uint_dtype
    idxs = jnp.broadcast_to(jnp.arange(bs, dtype=jnp.int32)[None, :], (nb, bs))
    ws = jnp.zeros((nb, bs), udt)
    for j in range(spec.itemsize):
        sh = jnp.asarray(8 * (spec.itemsize - 1 - j), udt)
        live = j < nbytes[:, None]
        if j >= spec.lead_cap:
            # L <= lead_cap <= j: every live value stores this plane itself
            byte = jnp.where(live, planes[:, j, :].astype(udt), jnp.asarray(0, udt))
            ws = ws | (byte << sh)
            continue
        stored = (L <= j) & live
        src = jnp.where(stored, idxs, -1)
        src = jax.lax.cummax(src, axis=1)              # index propagation
        byte = jnp.take_along_axis(
            planes[:, j, :].astype(udt), jnp.maximum(src, 0), axis=1
        )
        byte = jnp.where(src >= 0, byte, jnp.asarray(0, udt))
        ws = ws | (byte << sh)
    return _compose_word(ws, mu, shift, nbytes, spec)


def unpack_dense_ref(planes, mu, shift, nbytes, spec: DtypeSpec = specs.F32):
    """``unpack_ref`` specialized to all-zero L codes (no XOR-lead elision).

    With L == 0 every live plane byte (j < nbytes) is stored at its own value,
    so the index-propagation scan degenerates to a masked byte composition.
    Bit-identical to ``unpack_ref(planes, mu, shift, nbytes, L=0)``.
    """
    nb, _, bs = planes.shape
    udt = spec.uint_dtype
    ws = jnp.zeros((nb, bs), udt)
    for j in range(spec.itemsize):
        live = (nbytes > j)[:, None]
        byte = jnp.where(live, planes[:, j, :].astype(udt), jnp.asarray(0, udt))
        ws = ws | (byte << jnp.asarray(8 * (spec.itemsize - 1 - j), udt))
    return _compose_word(ws, mu, shift, nbytes, spec)


# ---------------------------------------------------------------------------
# Device-resident stream decode: the inverse of core.codec.device assembly.
# ---------------------------------------------------------------------------

def parse_body_ref(body, nnc, spec: DtypeSpec, nb: int):
    """On-device parse of the v2 metadata sections from raw stream bytes.

    ``body`` is the stream minus its 40-byte header -- ONE uint8 vector,
    zero-padded to a static capacity so chunk geometry (not payload size)
    decides the compiled program.  ``nnc`` is the header's n_nonconst field
    (traced scalar).  Section offsets are derived here exactly as the host
    serializer lays them out: ``[const bitmap][mu words][compacted reqlen]``.

    Returns (const, mu, shift, nbytes, rank, nnc_seen): per-block metadata
    (rank = compacted index of each non-const block, -1 for const) plus the
    bitmap's own nonconst count -- compared against the header's ``nnc`` on
    the host after the single readback (corrupt-stream validation).
    """
    W = spec.itemsize
    nbm = (nb + 7) // 8
    req_off = nbm + W * nb
    # const bitmap, MSB-first (numpy packbits order)
    bits = (body[:nbm][:, None] >> jnp.arange(7, -1, -1, dtype=jnp.uint8)) & 1
    const = bits.reshape(-1)[:nb].astype(bool)
    # mu words: little-endian bytes, the exact inverse of the encode-side
    # bitcast_convert_type(mu, uint8) scatter
    mu = jax.lax.bitcast_convert_type(
        body[nbm:req_off].reshape(nb, W), spec.np_dtype
    )
    nonconst = ~const
    incl = jnp.cumsum(nonconst.astype(jnp.int32))
    rank = jnp.where(nonconst, incl - 1, -1)
    ridx = jnp.clip(req_off + rank, 0, body.shape[0] - 1)
    reqlen = jnp.where(nonconst, body[ridx].astype(jnp.int32), 0)
    # layout derivation (Formula 5, Solution C) -- same as derive_layout
    shift = jnp.where(const, 0, (8 - reqlen % 8) % 8)
    nbytes = (reqlen + shift) // 8
    return const, mu, shift, nbytes, rank, incl[-1]


def gather_words_ref(body, nnc, lo, nbytes, rank, spec: DtypeSpec,
                     *, bs: int, rb: int, rebase: bool = False):
    """Gather each value's stored bytes straight out of raw body bytes.

    Expands the compacted 2-bit L codes and derives each value's mid-stream
    offset as the exclusive cumsum of ``nbytes - L``, then gathers the stored
    bytes out of ``body`` (no intermediate planes array).  ``lo`` is the
    first decoded block (traced); ``rb`` (static) blocks are produced.
    ``rebase=True`` reads the mid section as starting at block ``lo``'s
    first mid byte -- the store ROI buffer layout (metadata prefix + the
    requested blocks' mid range).

    Returns (words (rb, bs) ``spec.uint_dtype`` holding the stored bytes of
    each value (0 where elided), L (rb, bs) int32, mid_total int32): the
    full-stream mid byte count implied by the L codes, for host-side
    validation against the header's nmid after the single readback.
    """
    W = spec.itemsize
    nb = rank.shape[0]
    nbm = (nb + 7) // 8
    req_off = nbm + W * nb
    l_off = req_off + nnc
    nl = (nnc * bs + 3) // 4
    mid_off = l_off + nl
    cap = body.shape[0]
    # 2-bit L codes: little-endian 4 per byte, compacted over non-const blocks
    pos = rank[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)[None, :]
    live_blk = (rank >= 0)[:, None]
    lidx = jnp.clip(jnp.where(live_blk, l_off + pos // 4, 0), 0, cap - 1)
    code = (body[lidx].astype(jnp.int32) >> ((pos % 4) * 2)) & 3
    L = jnp.where(live_blk, code, 0)
    # mid-stream offsets: exclusive cumsum of per-value stored-byte counts,
    # as a cumsum within each block plus one across the block totals
    counts = jnp.maximum(nbytes[:, None] - L, 0)
    row = jnp.cumsum(counts, axis=1)
    blk = jnp.cumsum(row[:, -1])
    ends = row + (blk - row[:, -1])[:, None]
    start = ends - counts
    mid_total = blk[-1]
    base = mid_off - (
        jax.lax.dynamic_slice_in_dim(start, lo, 1, axis=0)[0, 0] if rebase else 0
    )

    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, lo, rb, axis=0)

    L, start, nbytes_r = sl(L), sl(start), sl(nbytes)
    udt = spec.uint_dtype
    words = jnp.zeros((rb, bs), udt)
    for j in range(W):
        stored = (L <= j) & (j < nbytes_r[:, None])
        gidx = jnp.clip(jnp.where(stored, base + start + (j - L), 0), 0, cap - 1)
        byte = jnp.where(stored, body[gidx], jnp.uint8(0)).astype(udt)
        words = words | (byte << jnp.asarray(8 * (W - 1 - j), udt))
    return words, L, mid_total


def unpack_words_ref(words, mu, shift, nbytes, L, spec: DtypeSpec):
    """:func:`unpack_ref` over stored-byte words (bytes a value elides are
    ignored): the fused-key cummax form of the index propagation."""
    rb, bs = words.shape
    W = spec.itemsize
    udt = spec.uint_dtype
    idxs = jnp.broadcast_to(jnp.arange(bs, dtype=jnp.int32)[None, :], (rb, bs))
    ws = jnp.zeros((rb, bs), udt)
    for j in range(W):
        sh = jnp.asarray(8 * (W - 1 - j), udt)
        stored = (L <= j) & (j < nbytes[:, None])
        byte = jnp.where(stored, ((words >> sh) & 0xFF).astype(jnp.int32), 0)
        if j >= spec.lead_cap:
            # L <= lead_cap <= j: every live value stores this plane itself
            ws = ws | (byte.astype(udt) << sh)
            continue
        # fused-key index propagation (idx dominates; the surviving key
        # carries the byte of the nearest preceding stored position)
        key = jnp.where(stored, idxs * 256 + byte, -1)
        key = jax.lax.cummax(key, axis=1)
        b = jnp.where(key >= 0, (key & 0xFF).astype(udt), jnp.asarray(0, udt))
        ws = ws | (b << sh)
    return _compose_word(ws, mu, shift, nbytes, spec)


# ---------------------------------------------------------------------------
# Fixed-plane ("szx-planes") in-graph mode -- see DESIGN.md section 2.
# ---------------------------------------------------------------------------

def planes_encode_ref(xb, num_planes: int):
    """Error-bounded-by-construction block quantization to `num_planes` bytes.

    xb: (nb, bs) f32.  Returns (mu (nb,) f32, sexp (nb,) int32, planes
    (num_planes, nb, bs) uint8).  q = rint(v * 2^sexp) with sexp chosen from the
    block radius exponent so |q| < 2^(8P-1); reconstruction error is
    <= 2^(E+1-8P) where E = p(radius).
    """
    assert 1 <= num_planes <= 3, "szx-planes supports 1..3 byte planes"
    xb = jnp.asarray(xb, jnp.float32)
    mn = jnp.min(xb, axis=-1)
    mx = jnp.max(xb, axis=-1)
    mu = 0.5 * (mn + mx)
    radius = jnp.maximum(mx - mu, mu - mn)
    E = f32_exponent(radius)
    nbits = 8 * num_planes
    sexp = (nbits - 2) - E
    v = xb - mu[..., None]
    scale = jnp.exp2(sexp.astype(jnp.float32))[..., None]
    lim = jnp.float32(2.0 ** (nbits - 1))
    q = jnp.clip(jnp.round(v * scale), -lim, lim - 1).astype(jnp.int32)
    uq = q.astype(jnp.uint32)
    planes = jnp.stack(
        [((uq >> (8 * p)) & jnp.uint32(0xFF)).astype(jnp.uint8) for p in range(num_planes)],
        axis=0,
    )
    return mu, sexp, planes


def planes_decode_ref(mu, sexp, planes):
    """Inverse of planes_encode_ref -> (..., bs) f32.  num_planes must be <= 3."""
    num_planes = planes.shape[0]
    assert num_planes <= 3, "szx-planes supports 1..3 byte planes"
    nbits = 8 * num_planes
    uq = jnp.zeros(planes.shape[1:], jnp.int32)
    for p in range(num_planes):
        uq = uq | (planes[p].astype(jnp.int32) << (8 * p))
    # sign-extend a width-`nbits` two's-complement integer (fits in int32)
    q = jnp.where(uq >= (1 << (nbits - 1)), uq - (1 << nbits), uq).astype(jnp.float32)
    v = q * jnp.exp2(-sexp.astype(jnp.float32))[..., None]
    return v + mu[..., None]


# ---------------------------------------------------------------------------
# bitplane shuffle (second-stage transform; see repro.kernels.bitshuffle)
# ---------------------------------------------------------------------------

def bitshuffle_ref(tiles, *, inverse: bool = False):
    """Bit-transpose (nt, T) uint8 tiles, T % 8 == 0 (little-endian packing).

    Ground truth for the Pallas kernel in ``bitshuffle.py``: forward places
    bit k of every tile byte contiguously (bit-row k); ``inverse`` undoes it.
    Bit-identical to ``np.unpackbits``/``np.packbits`` with
    ``bitorder='little'`` (pinned by tests against the numpy mirror).
    """
    from repro.kernels.bitshuffle import from_matrix, to_matrix, transpose8

    nt, T = tiles.shape
    if T % 8:
        raise ValueError(f"bitshuffle tile width {T} is not a multiple of 8")
    if nt == 0:
        return jnp.zeros((0, T), jnp.uint8)
    mat = to_matrix(jnp.asarray(tiles, jnp.uint8), inverse).astype(jnp.int32)
    out = jnp.stack(transpose8([mat[j] for j in range(8)])).astype(jnp.uint8)
    return from_matrix(out, nt, inverse)
