"""Limb-vectorized F_q arithmetic — the MEA-ECC hot path as array math.

The legacy crypto stack (kept as ``crypto/ref.py``) did per-element Python
big-int arithmetic through ``np.vectorize`` on object-dtype arrays, which
caps MEA-ECC at interpreter speed.  This module represents batches of F_q
elements as fixed-width little-endian **limb planes** — shape ``(..., L)``
``uint32`` (``L = 8`` for secp256k1), viewable as ``(..., L // 2)``
``uint64`` — and implements everything the cipher needs as vectorized
numpy/jnp ops:

* :func:`add_mod` / :func:`sub_mod` — limb adds with a sequential carry
  chain over the (tiny, static) limb axis and a *single* conditional
  subtract/add of q.  Both operands are always ``< q``, so sums are
  ``< 2q`` and one correction suffices — no Montgomery machinery.  Only
  ``uint32`` ops are used (TPU/XLA have no 64-bit ints by default), so the
  same code runs under numpy, XLA and Pallas (``xp`` parameter).
* :class:`FixedPointCodec` — the paper's ``round(x · 2^frac_bits) mod q``
  two's-complement embedding, float→limbs without ever materializing a
  Python int: the scaled float is decomposed exactly into a ≤53-bit
  mantissa and a power-of-two shift (``np.frexp``), and the shift becomes
  vectorized limb/bit shifts.
* :class:`BitsCodec` — lossless transport embedding: the raw little-endian
  bytes of *any* dtype as one ``uint32`` word per field element.  This is
  what makes ``encrypt → wire → decrypt`` bit-identical (the runtime's
  ``encrypt="real"`` mode and encrypted checkpoints).
* :func:`keystream_u64` — the stream-mode mask words from a **batched**
  SHA-256 counter PRF: the compression function runs vectorized over all
  counter blocks at once (pure uint32 numpy), bit-exact with the scalar
  ``hashlib`` reference in ``crypto.ecc.keystream``.
"""

from __future__ import annotations

import hashlib

import numpy as np

__all__ = [
    "LimbField", "FixedPointCodec", "BitsCodec",
    "int_to_limbs", "limbs_to_int", "add_mod", "sub_mod",
    "sha256_counter_blocks", "keystream_u64",
]

_MASK32 = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# limb <-> int conversions (host-side; ints only at the API edge)
# ---------------------------------------------------------------------------

def n_limbs_for(q: int) -> int:
    """Limbs needed for F_q elements, rounded up to an even count so the
    ``(..., L)`` uint32 planes view as ``(..., L // 2)`` uint64."""
    n = max((q.bit_length() + 31) // 32, 2)
    return n + (n % 2)


def int_to_limbs(v: int, n_limbs: int) -> np.ndarray:
    """Non-negative python int -> (n_limbs,) uint32, little-endian."""
    if v < 0:
        raise ValueError("limb encoding takes non-negative values")
    out = np.empty(n_limbs, np.uint32)
    for j in range(n_limbs):
        out[j] = v & _MASK32
        v >>= 32
    if v:
        raise OverflowError(f"value needs more than {n_limbs} limbs")
    return out


def limbs_to_int(limbs) -> object:
    """(..., L) limbs -> python ints (object array; scalar for 1-D input).
    Test/debug path — the hot path never calls this."""
    arr = np.asarray(limbs, np.uint32)
    flat = arr.reshape(-1, arr.shape[-1])
    vals = np.empty(flat.shape[0], object)
    for i, row in enumerate(flat):
        v = 0
        for j in range(arr.shape[-1] - 1, -1, -1):
            v = (v << 32) | int(row[j])
        vals[i] = v
    if arr.ndim == 1:
        return vals[0]
    return vals.reshape(arr.shape[:-1])


def as_u64(limbs: np.ndarray) -> np.ndarray:
    """(..., L) uint32 plane -> (..., L // 2) uint64 view (little-endian)."""
    return np.ascontiguousarray(limbs).view(np.uint64)


# ---------------------------------------------------------------------------
# vectorized modular add/sub (uint32-only; xp = numpy or jax.numpy)
# ---------------------------------------------------------------------------

def _add_carry(a, b, xp):
    """Limb-wise a + b with carry chain.  Returns (sum_limbs, carry_out)."""
    n = a.shape[-1]
    one = xp.uint32(1)
    carry = xp.zeros(a.shape[:-1], np.uint32)
    rows = []
    for j in range(n):
        aj, bj = a[..., j], b[..., j]
        s = aj + bj                              # wraps mod 2^32
        c1 = (s < aj).astype(np.uint32)
        s2 = s + carry
        c2 = (s2 < carry).astype(np.uint32)      # only wraps when s == 2^32-1
        rows.append(s2)
        carry = (c1 | c2) * one
    return xp.stack(rows, axis=-1), carry


def _sub_borrow(a, b, xp):
    """Limb-wise a - b with borrow chain.  Returns (diff_limbs, borrow_out)."""
    n = a.shape[-1]
    one = xp.uint32(1)
    borrow = xp.zeros(a.shape[:-1], np.uint32)
    rows = []
    for j in range(n):
        aj, bj = a[..., j], b[..., j]
        d = aj - bj                              # wraps mod 2^32
        b1 = (aj < bj).astype(np.uint32)
        d2 = d - borrow
        b2 = (d < borrow).astype(np.uint32)      # only wraps when d == 0
        rows.append(d2)
        borrow = (b1 | b2) * one
    return xp.stack(rows, axis=-1), borrow


def _geq(a, q_limbs, xp):
    """Lexicographic a >= q over (..., L) limbs; q_limbs broadcastable."""
    n = a.shape[-1]
    gt = xp.zeros(a.shape[:-1], bool)
    eq = xp.ones(a.shape[:-1], bool)
    for j in range(n - 1, -1, -1):
        qj = q_limbs[..., j]
        gt = gt | (eq & (a[..., j] > qj))
        eq = eq & (a[..., j] == qj)
    return gt | eq


def add_mod(a, b, q_limbs, xp=np):
    """(a + b) mod q over (..., L) uint32 limb planes; a, b < q."""
    s, carry = _add_carry(a, b, xp)
    # a + b < 2q: one conditional subtract of q (carry == the dropped 2^32L)
    ge = (carry.astype(bool)) | _geq(s, q_limbs, xp)
    red, _ = _sub_borrow(s, xp.broadcast_to(q_limbs, s.shape).astype(np.uint32), xp)
    return xp.where(ge[..., None], red, s)


def sub_mod(a, b, q_limbs, xp=np):
    """(a - b) mod q over (..., L) uint32 limb planes; a, b < q."""
    d, borrow = _sub_borrow(a, b, xp)
    fix, _ = _add_carry(d, xp.broadcast_to(q_limbs, d.shape).astype(np.uint32), xp)
    return xp.where(borrow.astype(bool)[..., None], fix, d)


# ---------------------------------------------------------------------------
# the field handle
# ---------------------------------------------------------------------------

class LimbField:
    """F_q as fixed-width uint32 limb planes (see module docstring)."""

    def __init__(self, q: int):
        self.q = q
        self.n_limbs = n_limbs_for(q)
        self.q_limbs = int_to_limbs(q, self.n_limbs)

    def add(self, a, b):
        return add_mod(np.asarray(a, np.uint32), np.asarray(b, np.uint32),
                       self.q_limbs)

    def sub(self, a, b):
        return sub_mod(np.asarray(a, np.uint32), np.asarray(b, np.uint32),
                       self.q_limbs)

    def from_int(self, v: int, shape=()) -> np.ndarray:
        """Python int -> limbs broadcast to ``shape + (L,)``."""
        base = int_to_limbs(v % self.q, self.n_limbs)
        return np.broadcast_to(base, tuple(shape) + (self.n_limbs,)).copy()

    def from_u64(self, words: np.ndarray) -> np.ndarray:
        """(…,) uint64 words (< q after reduction) -> (…, L) limb planes."""
        words = np.asarray(words, np.uint64)
        if self.q.bit_length() <= 64:
            words = words % np.uint64(self.q)
        out = np.zeros(words.shape + (self.n_limbs,), np.uint32)
        out[..., 0] = (words & np.uint64(_MASK32)).astype(np.uint32)
        out[..., 1] = (words >> np.uint64(32)).astype(np.uint32)
        return out

    def to_ints(self, limbs) -> np.ndarray:
        return limbs_to_int(limbs)


# ---------------------------------------------------------------------------
# fixed-point codec (paper §IV-B embedding), float <-> limbs
# ---------------------------------------------------------------------------

class FixedPointCodec:
    """round(x · 2^frac_bits) mod q, two's-complement embedded in F_q.

    Bit-exact with the legacy big-int codec (``crypto.ref``) for float
    inputs, but fully vectorized: the scaled magnitude is decomposed as
    ``mant · 2^shift`` with ``mant < 2^53`` exactly (``np.frexp``), the
    mantissa split into 32-bit limbs and the power-of-two shift applied as
    limb/bit shifts.  Decode reconstructs the float by a Horner pass over
    the limbs and clamps to ±3e38 (wrong-key decrypts yield huge values).
    """

    CLAMP = 3e38

    def __init__(self, q: int, frac_bits: int = 16):
        # magnitudes scale to < 2^(136 + frac_bits) (see encode's clip); the
        # embedding needs headroom below q/2 for the sign
        if q.bit_length() < 138 + frac_bits:
            raise ValueError(
                f"FixedPointCodec needs a ≥{138 + frac_bits}-bit modulus for "
                f"float32 range; got {q.bit_length()} bits (use BitsCodec or "
                "a bigger curve)")
        self.field = LimbField(q)
        self.q = q
        self.frac_bits = frac_bits
        # v is negative iff v > q//2, i.e. v >= q//2 + 1
        self._neg_from = int_to_limbs(q // 2 + 1, self.field.n_limbs)

    # -- float -> limbs ----------------------------------------------------
    def encode(self, m: np.ndarray) -> np.ndarray:
        x = np.asarray(np.asarray(m), np.float64)
        # float64 inputs beyond f32 range would overflow the 3-limb scatter
        # below; 2^136 exceeds every float32 so in-range values (the parity
        # contract with the legacy codec) are untouched
        scaled = np.rint(np.clip(x, -2.0 ** 136, 2.0 ** 136) *
                         float(1 << self.frac_bits))
        neg = scaled < 0
        mag = np.abs(scaled)
        # exact decomposition mag = mant_i * 2^shift with mant_i < 2^53
        mant, exp = np.frexp(mag)
        small = exp <= 53
        mant_f = np.where(small, mag, mant * float(1 << 53))
        mant_i = mant_f.astype(np.uint64)
        shift = np.where(small, 0, exp - 53).astype(np.int64)
        L = self.field.n_limbs
        s_limb = (shift // 32).astype(np.int64)
        r = (shift % 32).astype(np.uint64)
        # mant_i << r spans up to 84 bits -> three 32-bit limbs l0,l1,l2
        lo64 = mant_i << r
        hi = (mant_i >> np.uint64(32)) >> (np.uint64(32) - r)   # == >> (64-r)
        l0 = (lo64 & np.uint64(_MASK32)).astype(np.uint32)
        l1 = (lo64 >> np.uint64(32)).astype(np.uint32)
        l2 = (hi & np.uint64(_MASK32)).astype(np.uint32)
        out = np.zeros(x.shape + (L,), np.uint32)
        for j in range(L):
            out[..., j] = np.where(
                s_limb == j, l0,
                np.where(s_limb == j - 1, l1,
                         np.where(s_limb == j - 2, l2, np.uint32(0))))
        # negative values embed as q - |v| (v < q guaranteed by the
        # modulus-size check above); zero stays zero
        nonzero = mag > 0
        neg_embed = sub_mod(np.broadcast_to(self.field.q_limbs, out.shape),
                            out, self.field.q_limbs)
        return np.where((neg & nonzero)[..., None], neg_embed, out)

    # -- limbs -> float ----------------------------------------------------
    def decode(self, limbs: np.ndarray) -> np.ndarray:
        limbs = np.asarray(limbs, np.uint32)
        neg = _geq(limbs, self._neg_from, np)            # v > q//2
        mag = np.where(
            neg[..., None],
            sub_mod(np.broadcast_to(self.field.q_limbs, limbs.shape),
                    limbs, self.field.q_limbs),
            limbs)
        val = np.zeros(limbs.shape[:-1], np.float64)
        for j in range(limbs.shape[-1] - 1, -1, -1):     # Horner, high→low
            val = val * float(1 << 32) + mag[..., j]
        val = np.where(neg, -val, val) / float(1 << self.frac_bits)
        return np.clip(val, -self.CLAMP, self.CLAMP).astype(np.float32)


# ---------------------------------------------------------------------------
# lossless transport codec: raw bytes <-> one uint32 word per element
# ---------------------------------------------------------------------------

class BitsCodec:
    """Embed the raw little-endian bytes of any array as uint32 field
    elements — ``decode(encode(x)) is bit-identical`` for every dtype.

    This is the transport embedding the runtime's ``encrypt="real"`` mode
    and the encrypted checkpointer use: transmission security does not need
    the fixed-point quantization, only that the wire bits round-trip.
    """

    def __init__(self, q: int):
        if q.bit_length() <= 32:
            raise ValueError("BitsCodec needs q > 2^32 (one uint32/elem)")
        self.field = LimbField(q)
        self.q = q

    def encode_words(self, m: np.ndarray) -> np.ndarray:
        """array -> (n_words,) uint32 raw words (4 little-endian bytes each)."""
        raw = np.ascontiguousarray(m).tobytes()
        pad = (-len(raw)) % 4
        return np.frombuffer(raw + b"\x00" * pad, np.uint32)

    def decode_words(self, words: np.ndarray, dtype, shape) -> np.ndarray:
        try:
            dtype = np.dtype(dtype)
        except TypeError:       # extension dtypes by name ("bfloat16", ...)
            import ml_dtypes
            dtype = np.dtype(getattr(ml_dtypes, str(dtype)))
        nbytes = int(np.prod(shape, initial=1)) * dtype.itemsize
        raw = np.ascontiguousarray(np.asarray(words, np.uint32)).tobytes()
        return np.frombuffer(raw[:nbytes], dtype).reshape(shape).copy()

    def encode(self, m: np.ndarray) -> np.ndarray:
        """array -> (n_words, L) limb planes (word in limb 0)."""
        words = self.encode_words(m)
        out = np.zeros((words.size, self.field.n_limbs), np.uint32)
        out[:, 0] = words
        return out

    def decode(self, limbs: np.ndarray, dtype, shape) -> np.ndarray:
        return self.decode_words(limbs[..., 0], dtype, shape)


# ---------------------------------------------------------------------------
# batched SHA-256 counter PRF (stream-mode keystream)
# ---------------------------------------------------------------------------

_SHA_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2], np.uint32)

_SHA_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19], np.uint32)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _sha256_single_block(w16, xp):
    """The SHA-256 compression of one 64-byte block, vectorized over a batch.

    ``w16``: list of 16 uint32 arrays (broadcast-compatible) — the message
    schedule base.  Returns list of 8 uint32 digest-word arrays.  xp-generic
    (numpy or jax.numpy): uint32 adds wrap, shifts/xors are elementwise.
    """
    w = list(w16)
    for t in range(16, 64):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ (w[t - 15] >> np.uint32(3))
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ (w[t - 2] >> np.uint32(10))
        w.append(w[t - 16] + s0 + w[t - 7] + s1)
    a, bb, c, d, e, f, g, h = (xp.asarray(v, np.uint32) for v in _SHA_H0)
    for t in range(64):
        S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + S1 + ch + np.uint32(_SHA_K[t]) + w[t]
        S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & bb) ^ (a & c) ^ (bb & c)
        t2 = S0 + maj
        h, g, f, e, d, c, bb, a = g, f, e, d + t1, c, bb, a, t1 + t2
    return [x + np.uint32(h0) for x, h0 in zip([a, bb, c, d, e, f, g, h],
                                               _SHA_H0)]


def _counter_schedule(seed_words, counters_lo, counters_hi, xp):
    """Message-schedule base for SHA-256(seed32 ‖ counter_be64): 40 message
    bytes + mandatory padding in one 64-byte block."""
    w16 = [xp.asarray(seed_words[i], np.uint32) for i in range(8)]
    w16 += [counters_hi, counters_lo]
    zero = xp.zeros_like(counters_lo)
    w16 += [zero + np.uint32(0x80000000)]           # pad bit after 40 bytes
    w16 += [zero, zero, zero, zero]
    w16 += [zero + np.uint32(40 * 8)]               # message bit length
    return w16


def sha256_counter_blocks(seed32: bytes, counters: np.ndarray) -> np.ndarray:
    """SHA-256(seed32 ‖ counter_be64) for a whole batch of counters at once.

    One 64-byte block per message, compression vectorized over the counter
    axis with uint32 numpy ops.  Returns ``(len(counters), 8)`` uint32
    digest words — bit-exact with
    ``hashlib.sha256(seed + c.to_bytes(8, "big")).digest()``.
    """
    assert len(seed32) == 32
    counters = np.asarray(counters, np.uint64)
    seed_words = np.frombuffer(seed32, ">u4").astype(np.uint32)
    w16 = _counter_schedule(seed_words,
                            (counters & np.uint64(_MASK32)).astype(np.uint32),
                            (counters >> np.uint64(32)).astype(np.uint32), np)
    with np.errstate(over="ignore"):        # uint32 wraparound is the point
        return np.stack(_sha256_single_block(w16, np), axis=1)


def seed_words(secret_x, secret_y, nonce: int) -> np.ndarray:
    """The stream-mode PRF seed — SHA-256 of the ECDH point and nonce — as
    big-endian uint32 words ((8,), host-side)."""
    seed = hashlib.sha256(f"{secret_x}:{secret_y}:{nonce}".encode()).digest()
    return np.frombuffer(seed, ">u4").astype(np.uint32)


# ---------------------------------------------------------------------------
# traced (jnp) twins — the XLA cipher core building blocks
# ---------------------------------------------------------------------------
# These mirror the numpy reference implementations above inside a jit trace,
# uint32-only (XLA/TPU have no 64-bit ints by default), so the whole
# encrypt/decrypt direction fuses into one elementwise XLA program.  Parity
# with the numpy/legacy paths is asserted in tests/test_crypto.py.

def _sha_round_step(carry, k):
    """One SHA-256 compression round over a lane vector; scanned 64×.

    ``carry`` is the 16-slot message-schedule window (as a tuple, rotated
    by static position — no dynamic indexing anywhere, which is what the
    rolled ``fori_loop`` twin paid ~4× runtime for) followed by the 8-word
    hash state.
    """
    w, (a, bb, c, d, e, f, g, h) = carry[:16], carry[16:]
    wt = w[0]
    S1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = h + S1 + ch + k + wt
    S0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & bb) ^ (a & c) ^ (bb & c)
    s0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ (w[1] >> np.uint32(3))
    s1 = _rotr(w[14], 17) ^ _rotr(w[14], 19) ^ (w[14] >> np.uint32(10))
    # slot 0 holds w[t]; the rotation drops it and appends w[t+16]
    wn = wt + s0 + w[9] + s1
    return w[1:] + (wn, t1 + S0 + maj, a, bb, c, d + t1, e, f, g), None


# Lanes per inner SHA scan: the 24-array carry is lane_chunk*24*4 bytes
# (384 KB at 4096), small enough to stay cache-resident across the 64
# rounds.  One big scan over 100k+ lanes spills the carry to memory every
# round and runs ~2.3× slower end to end (measured on the fig-3 wide
# wire-back: 30 channels × 8192 blocks).
_LANE_CHUNK = 4096


def keystream_words_traced_batched(seeds, n_words: int,
                                   lane_chunk: int = _LANE_CHUNK):
    """(C, 8) uint32 seed-word channels -> ((C, n_words), (C, n_words))
    uint32 mask word halves (lo, hi); channel i's u64 stream-mask word j is
    ``hi[i, j] << 32 | lo[i, j]``.

    In-trace batched SHA-256 counter PRF (per-channel counters from iota;
    < 2^32 blocks), bit-exact with :func:`keystream_u64` per channel.  All
    (channel, block) lanes are flattened into one lane axis and processed
    ``lane_chunk`` at a time by an outer scan whose body runs the 64-round
    compression scan — chunking keeps the 24-array round carry in cache
    (see ``_LANE_CHUNK``), which is why this exists instead of
    ``jax.vmap(keystream_words_traced)``.
    """
    import jax
    import jax.numpy as jnp
    n_ch = seeds.shape[0]
    n_blocks = max(-(-n_words // 4), 1)
    lanes = n_ch * n_blocks
    seeds = jnp.asarray(seeds, jnp.uint32)
    ks = jnp.asarray(_SHA_K)

    def digest_of(lane):
        # each lane's message schedule is built where it is hashed (lane =
        # channel * n_blocks + counter): the digest is the only lane-sized
        # array the keystream materializes
        seed_lanes = seeds[lane // n_blocks]                 # (L, 8)
        counter = lane % n_blocks
        w16 = tuple(jnp.broadcast_to(jnp.asarray(w, jnp.uint32), lane.shape)
                    for w in _counter_schedule(
                        [seed_lanes[:, i] for i in range(8)], counter,
                        jnp.zeros_like(counter), jnp))
        h0 = tuple(jnp.broadcast_to(jnp.uint32(v), lane.shape)
                   for v in _SHA_H0)
        carry, _ = jax.lax.scan(_sha_round_step, w16 + h0, ks)
        digest = [v + jnp.uint32(h) for v, h in zip(carry[16:], _SHA_H0)]
        # digest words pair big-endian into u64 mask words w = d0<<32 | d1,
        # four per lane, interleaved here: a lane-sized (L, 4) array would
        # pad its minor dim of 4 to a full 128-wide tile on TPU (32x)
        return (jnp.stack(digest[1::2], axis=1).reshape(-1),
                jnp.stack(digest[0::2], axis=1).reshape(-1))

    if lanes <= lane_chunk:
        word_lo, word_hi = digest_of(jnp.arange(lanes, dtype=jnp.uint32))
    else:
        n_chunks = -(-lanes // lane_chunk)
        base = jnp.arange(lane_chunk, dtype=jnp.uint32)

        def chunk_body(_, c):
            # lanes past the end (last chunk) gather a clamped seed and are
            # sliced off below
            return None, digest_of(c * jnp.uint32(lane_chunk) + base)

        _, (word_lo, word_hi) = jax.lax.scan(
            chunk_body, None, jnp.arange(n_chunks, dtype=jnp.uint32))
    word_lo = word_lo.reshape(-1)[:4 * lanes].reshape(n_ch, -1)
    word_hi = word_hi.reshape(-1)[:4 * lanes].reshape(n_ch, -1)
    return word_lo[:, :n_words], word_hi[:, :n_words]


def keystream_words_traced(seed8, n_words: int):
    """(8,) uint32 seed words -> ((n_words,), (n_words,)) uint32 mask word
    halves (lo, hi): the u64 stream-mask word for payload word i is
    ``hi[i] << 32 | lo[i]``.

    Single-channel face of :func:`keystream_words_traced_batched` (same
    scan, same cache-chunking, bit-exact with :func:`keystream_u64`).  The
    scan keeps the jit graph ~50 ops (new shard shapes compile in well
    under a second) while running within ~2× of the unrolled numpy batch.
    """
    import jax.numpy as jnp
    lo, hi = keystream_words_traced_batched(
        jnp.asarray(seed8, jnp.uint32)[None, :], n_words)
    return lo[0], hi[0]


def stream_mask_traced(seed8, n_words: int, n_limbs: int):
    """(8,) uint32 seed words -> (n_words, n_limbs) stream-mask limb planes.

    Limb form of :func:`keystream_words_traced`: little-endian limbs of the
    u64 mask words are (lo, hi); high limbs are zero.  No modular
    reduction: the 64-bit mask words are < q for any modulus wider than
    64 bits (the caller falls back to the numpy path otherwise).
    """
    import jax.numpy as jnp
    word_lo, word_hi = keystream_words_traced(seed8, n_words)
    zero = jnp.zeros_like(word_lo)
    return jnp.stack([word_lo, word_hi] + [zero] * (n_limbs - 2), axis=-1)


def fixed_encode_traced(x, q: int, frac_bits: int, n_limbs: int):
    """Traced fixed-point embed: (n,) float32 -> (n, n_limbs) uint32 limbs.

    Bit-exact with :meth:`FixedPointCodec.encode` for f32/f16/bf16 inputs
    (the scale-by-2^frac_bits happens in exponent space, so nothing
    overflows float32 even at the clamp).  uint32-only: the float is torn
    into sign/exponent/24-bit mantissa and round-half-even + the limb
    scatter are bit arithmetic.
    """
    import jax
    import jax.numpy as jnp
    f32max = jnp.float32(3.4028235e38)
    x = jnp.clip(jnp.asarray(x, jnp.float32).reshape(-1), -f32max, f32max)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = (bits >> np.uint32(31)) == 1
    e = ((bits >> np.uint32(23)) & np.uint32(0xFF)).astype(jnp.int32)
    mant = (bits & np.uint32(0x7FFFFF)) | jnp.where(
        e > 0, np.uint32(1 << 23), np.uint32(0))
    # v = round(|x| * 2^fb) = round-half-even(mant * 2^(e - 150 + fb))
    ep = e - (150 - frac_bits)
    # right-shift branch (ep < 0): t <= 26 covers everything (v == 0 beyond)
    t = jnp.clip(-ep, 0, 26).astype(jnp.uint32)
    keep = mant >> t
    frac = mant & ((np.uint32(1) << t) - np.uint32(1))
    half = jnp.where(t > 0, np.uint32(1) << (t - np.uint32(1)), np.uint32(0))
    round_up = (frac > half) | ((frac == half) & ((keep & 1) == 1))
    v_small = keep + round_up.astype(jnp.uint32)
    # left-shift branch (ep >= 0): mant << ep spans limbs s, s+1
    r = jnp.maximum(ep, 0).astype(jnp.uint32) % np.uint32(32)
    s = jnp.maximum(ep, 0) // 32
    lo = mant << r
    hi = jnp.where(r > 0, mant >> (np.uint32(32) - r), np.uint32(0))
    left = ep >= 0
    l0 = jnp.where(left, lo, v_small)
    out = jnp.stack(
        [jnp.where(s == j, l0,
                   jnp.where(left & (s == j - 1), hi, np.uint32(0)))
         for j in range(n_limbs)], axis=-1)
    # negative values embed as q - v
    q_limbs = tuple(int(v) for v in int_to_limbs(q, n_limbs))
    qarr = jnp.asarray(np.asarray(q_limbs, np.uint32))
    neg_embed = sub_mod(jnp.broadcast_to(qarr, out.shape), out, qarr, xp=jnp)
    nonzero = jnp.any(out != 0, axis=-1)
    return jnp.where((sign & nonzero)[:, None], neg_embed, out)


def fixed_decode_traced(limbs, q: int, frac_bits: int):
    """Traced fixed-point decode: (n, L) uint32 limbs -> (n,) float32.

    Matches :meth:`FixedPointCodec.decode` wherever the value has ≤ 24
    significant bits (everything `encode` can emit) and on the ±3e38 clamp
    (wrong-key garbage); only pathological >24-bit unclamped values may
    differ by float32 rounding.
    """
    import jax.numpy as jnp
    limbs = jnp.asarray(limbs, jnp.uint32)
    L = limbs.shape[-1]
    neg_from = jnp.asarray(int_to_limbs(q // 2 + 1, L))
    neg = _geq(limbs, neg_from, jnp)
    qarr = jnp.asarray(int_to_limbs(q, L))
    mag = jnp.where(neg[..., None],
                    sub_mod(jnp.broadcast_to(qarr, limbs.shape), limbs, qarr,
                            xp=jnp),
                    limbs)
    # Horner over limbs 1.. (value/2^32), then fold limb 0 and the
    # fixed-point scale in one final step: the full integer value can reach
    # 2^(128 + frac_bits), beyond float32 — but value/2^frac_bits is in
    # float32 range whenever the plaintext was (garbage overflows to inf
    # and lands on the clamp, matching the reference decoder)
    val_hi = jnp.zeros(limbs.shape[:-1], jnp.float32)
    for j in range(L - 1, 0, -1):
        val_hi = val_hi * jnp.float32(1 << 32) + mag[..., j].astype(jnp.float32)
    val = (val_hi * jnp.float32(2.0 ** (32 - frac_bits)) +
           mag[..., 0].astype(jnp.float32) * jnp.float32(2.0 ** -frac_bits))
    val = jnp.where(neg, -val, val)
    clamp = jnp.float32(FixedPointCodec.CLAMP)
    return jnp.clip(val, -clamp, clamp)


def keystream_u64(secret_x, secret_y, nonce: int, n_words: int, q: int) -> np.ndarray:
    """Vectorized stream-mode mask words: ``(n_words,)`` uint64, reduced
    mod q when q fits 64 bits (a no-op for 256-bit curves).  Bit-exact with
    the scalar ``crypto.ecc.keystream`` reference."""
    seed = hashlib.sha256(f"{secret_x}:{secret_y}:{nonce}".encode()).digest()
    n_blocks = -(-n_words // 4)
    if n_blocks == 0:
        return np.zeros(0, np.uint64)
    digests = sha256_counter_blocks(seed, np.arange(n_blocks, dtype=np.uint64))
    words = ((digests[:, 0::2].astype(np.uint64) << np.uint64(32)) |
             digests[:, 1::2].astype(np.uint64)).reshape(-1)[:n_words]
    if q.bit_length() <= 64:
        words = words % np.uint64(q)
    return words
