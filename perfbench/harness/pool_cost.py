"""Operations and bytes the three miss programs of the verifier's table
pool need, from the batch's shapes alone (keys built, lanes on the
ladder) — never from which kernel served them, nor from the bucket a
program was padded to: a program that pads one key to 128 does 128 keys'
work for one key's worth, and reads a LOWER share of the roofline, which
is the point. Field arithmetic is counted as `verify_cost.py` counts it
(a multiplication of two 255-bit elements in 32 limbs of 8 bits is 2048
operations).

Build, per key: the table is 64 window positions x 16 entries of -A's
multiples in niels form. The cheapest way the repository knows: at each
position 14 point additions make the entries 2..15 from the first (9
field multiplications each) and 4 doublings (8 each) carry the point to
the next position; one Montgomery batch inversion brings the 960
non-trivial entries to affine (3 multiplications an entry and one
inversion of 254 squarings and 11 multiplications a key), and forming
(y-x, y+x, 2dxy) costs 4 more an entry. Bytes: the key's two coordinates
read, its table written once in the pool's bf16 (1024 x 96 x 2).

Pool update, per key: no arithmetic; the built table read in f32 and
written in bf16.

Ladder, per lane: a key with no table is verified by the 2-bit joint
(Straus) ladder over [s]B + [h](-A): 254 doublings and 127 additions,
then the inversion and the encoding as in `verify_cost.py`. Bytes: R, s,
h, A, the message and the verdict.
"""

from __future__ import annotations

from harness.verify_cost import MULS_INVERT_ENCODE, OPS_PER_FIELD_MUL

POSITIONS, ENTRIES = 64, 16
MULS_ADD, MULS_DOUBLE = 9, 8
TABLE_BYTES_BF16 = POSITIONS * ENTRIES * 96 * 2
TABLE_BYTES_F32 = 2 * TABLE_BYTES_BF16


def build_field_muls_per_key() -> int:
    chain = POSITIONS * ((ENTRIES - 2) * MULS_ADD + 4 * MULS_DOUBLE)
    entries = POSITIONS * (ENTRIES - 1)
    return chain + entries * (3 + 4) + 254 + 11


def ladder_field_muls_per_lane() -> int:
    return 254 * MULS_DOUBLE + 127 * MULS_ADD + MULS_INVERT_ENCODE


def cost(kind: str, n: int, message_bytes: int = 0) -> tuple[float, float]:
    """(operations, bytes moved) of `n` keys built, `n` keys' slots
    updated, or `n` lanes on the ladder."""
    if kind == "build":
        return (float(n) * build_field_muls_per_key() * OPS_PER_FIELD_MUL,
                float(n) * (64 + TABLE_BYTES_BF16))
    if kind == "update":
        return 0.0, float(n) * (TABLE_BYTES_F32 + TABLE_BYTES_BF16 + 4)
    if kind == "ladder":
        return (float(n) * ladder_field_muls_per_lane() * OPS_PER_FIELD_MUL,
                float(n) * (4 * 32 + message_bytes + 1))
    raise ValueError(f"no cost for {kind!r}")


def least_seconds(kind: str, n: int, peaks: dict,
                  message_bytes: int = 0) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    ops, moved = cost(kind, n, message_bytes)
    t_ops, t_mem = ops / peaks["flops_per_s"], moved / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
