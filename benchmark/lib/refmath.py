"""What the plain references share: rounding to bfloat16, the logistic
functions, "the later write of a row stays", sums of squares in chunks, and
the configuration's key -> row rule. The references themselves stand beside
their models in ``benchmark/models/``. Nothing here imports the program.
"""

import numpy as np

def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to bfloat16 (nearest even), kept as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = ((u >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((u + r) & np.uint32(0xFFFF0000)).view(np.float32)


def sigmoid(x):
    return 0.5 * (1.0 + np.tanh(0.5 * x))


def log_sigmoid(x):
    return -np.logaddexp(0.0, -x)


def assign_last(table, rows, vals):
    """table[rows] = vals where, of a row named twice, the later slot wins."""
    u, first_in_reversed = np.unique(rows[::-1], return_index=True)
    table[u] = vals[::-1][first_in_reversed]


def murmur_fmix64(keys: np.ndarray) -> np.ndarray:
    """MurmurHash3's 64-bit finalizer (public algorithm), on uint64."""
    x = np.asarray(keys).astype(np.uint64)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xFF51AFD7ED558CCD)
        x ^= x >> np.uint64(33)
        x *= np.uint64(0xC4CEB9FE1A85EC53)
        x ^= x >> np.uint64(33)
    return x


def row_of_key(keys: np.ndarray, capacity: int) -> np.ndarray:
    """The configuration's key -> row rule: murmur3 fmix64(key) mod capacity."""
    return (murmur_fmix64(keys) % np.uint64(capacity)).astype(np.int64)


def sumsq(a, b, chunk=1 << 16):
    """sum((a - b)**2) in float64, ``b`` None for zeros, in chunks of rows."""
    total = 0.0
    for lo in range(0, len(a), chunk):
        d = a[lo:lo + chunk].astype(np.float64)
        if b is not None:
            d = d - b[lo:lo + chunk]
        total += float(np.sum(d * d))
    return total
