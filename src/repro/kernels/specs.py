"""Width-generic dtype specifications for the SZx kernel layer.

One :class:`DtypeSpec` carries everything the transform needs to run on a
given IEEE-754 float format: the on-stream dtype ``code`` (container header
byte), the storage word geometry (``itemsize``/``exp_bits``/``mant_bits``),
and the *compute* geometry -- the dtype the per-block statistics run in.

Storage vs compute dtype
------------------------
Stats (min/max/mu/radius) run in the **compute dtype**: float32 for words of
up to 4 bytes, float64 for float64.  The two 16-bit formats are exact subsets
of float32, so their stats lose nothing to the upcast while staying
expressible on accelerators that have no 64-bit words.  The binary exponent
``p(x) = floor(log2 x)`` is read from the compute dtype's exponent bit field
(conservative ``-bias`` for zero/subnormals, exactly like the original f32
path); the scalar error-bound exponent ``p(e)`` is computed exactly on the
host (``math.frexp``) and passed into the kernels.

float64 needs 64-bit words, which jax disables by default; the dispatch layer
(``repro.kernels.ops``) runs those calls under ``jax.enable_x64(True)``.
This module is the bottom of the stack: it must not import from
``repro.core``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

try:  # bfloat16 is a numpy extension dtype shipped by ml_dtypes (a jax dep)
    import ml_dtypes

    _BFLOAT16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    _BFLOAT16 = None


@dataclass(frozen=True)
class DtypeSpec:
    """IEEE-754 geometry of one supported input dtype.

    ``code`` is the on-stream dtype id (container header byte); the remaining
    fields parameterize the width-generic transform: required-bit computation
    uses ``exp_bits``/``mant_bits``, the byte-plane split uses ``itemsize``.
    Instances are frozen and hashable, so they are valid jit static args.
    """

    code: int
    name: str
    np_dtype: np.dtype
    uint_dtype: np.dtype
    itemsize: int
    exp_bits: int
    mant_bits: int
    exp_bias: int

    @property
    def word_bits(self) -> int:
        return 8 * self.itemsize

    @property
    def lead_cap(self) -> int:
        """Max XOR-lead elision count: the 2-bit L code caps at 3, a 2-byte
        word at its own plane count."""
        return min(3, self.itemsize)

    # ------------------------------------------------------------ compute side
    @property
    def needs_x64(self) -> bool:
        return self.itemsize == 8

    @property
    def compute_np_dtype(self) -> np.dtype:
        return np.dtype(np.float64) if self.itemsize == 8 else np.dtype(np.float32)

    @property
    def compute_uint_dtype(self) -> np.dtype:
        return np.dtype(np.uint64) if self.itemsize == 8 else np.dtype(np.uint32)

    @property
    def compute_mant_bits(self) -> int:
        return 52 if self.itemsize == 8 else 23

    @property
    def compute_exp_bits(self) -> int:
        return 11 if self.itemsize == 8 else 8

    @property
    def compute_exp_bias(self) -> int:
        return 1023 if self.itemsize == 8 else 127

    @property
    def stats_rounding_guard(self) -> bool:
        """True for the 16-bit formats, whose stats run in a WIDER compute
        dtype: the radius subtraction can still round below the true block
        deviation (f32 holds any f16/bf16 value exactly, but not every
        difference of two of them), so the constant-block test compares the
        next-representable-up radius against ``e`` to keep the bound strict.
        f32/f64 compute in their own width and keep the paper's exact-width
        semantics (f32 is golden-bytes pinned)."""
        return self.compute_np_dtype != self.np_dtype


F32 = DtypeSpec(0, "float32", np.dtype(np.float32), np.dtype(np.uint32), 4, 8, 23, 127)
F64 = DtypeSpec(1, "float64", np.dtype(np.float64), np.dtype(np.uint64), 8, 11, 52, 1023)
F16 = DtypeSpec(2, "float16", np.dtype(np.float16), np.dtype(np.uint16), 2, 5, 10, 15)

SPECS = [F32, F64, F16]
if _BFLOAT16 is not None:
    BF16 = DtypeSpec(3, "bfloat16", _BFLOAT16, np.dtype(np.uint16), 2, 8, 7, 127)
    SPECS.append(BF16)
else:  # pragma: no cover - ml_dtypes ships with jax
    BF16 = None

BY_CODE = {s.code: s for s in SPECS}
BY_DTYPE = {s.np_dtype: s for s in SPECS}


def spec_for(dtype) -> DtypeSpec:
    spec = BY_DTYPE.get(np.dtype(dtype))
    if spec is None:
        raise TypeError(
            f"unsupported dtype {np.dtype(dtype)}; supported: "
            + ", ".join(s.name for s in SPECS)
        )
    return spec


def spec_for_code(code: int) -> DtypeSpec:
    spec = BY_CODE.get(int(code))
    if spec is None:
        raise ValueError(f"unknown dtype code {code} in SZx stream")
    return spec


def exact_exponent_of(e: float) -> int:
    """Exact floor(log2 e) of a positive python float (Formula 4's p(e))."""
    m, ex = math.frexp(e)  # e = m * 2**ex with 0.5 <= m < 1
    return ex - 1
