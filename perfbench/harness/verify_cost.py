"""Operations and bytes ONE batch of Ed25519 verifications needs, from
the batch's shapes alone (lanes, message bytes, distinct keys) — never
from which kernel served it.

The yardstick is the cheapest algorithm this repository knows for a key
that signs again and again (every validator key does): the equation
[s]B + [h](-A) == R evaluated with 4-bit windows over tables of B and of
-A, i.e. 128 table entries looked up and 127 mixed point additions, then
one inversion to compare with the encoded R. A kernel that does more
work than this for the same lanes (doublings, a wider ladder) reads a
LOWER share of the roofline, which is the point.

Counting, per lane:
- a mixed (niels) point addition is 7 field multiplications;
- the inversion is 254 squarings and 11 multiplications, and encoding
  the result 2 more;
- a field multiplication of two 255-bit elements held as 32 limbs of 8
  bits (the widest limb a bf16 multiplier takes exactly) is 32 x 32
  multiply-adds = 2048 operations.
SHA-512 of R || A || M is host work in this system and is not counted as
device operations; the message's bytes are counted as bytes moved so that
a design that hashes on the device is not charged less.

Bytes, per lane: R, s and h (32 each), the 4-byte table slot, the
message, the 1-byte verdict, and the 64 entries of the key's table that
the lane's digits select (three 32-limb coordinates of 2 bytes each).
Per DISTINCT key nothing more: the tables are resident, and building
them is set-up.
"""

from __future__ import annotations

WINDOWS = 64                 # 4-bit windows over a 256-bit scalar
POINT_ADDS = 2 * WINDOWS - 1
MULS_PER_ADD = 7
MULS_INVERT_ENCODE = 254 + 11 + 2
OPS_PER_FIELD_MUL = 2 * 32 * 32
ENTRY_BYTES = 3 * 32 * 2     # (y-x, y+x, 2dxy) x 32 limbs x bf16


def field_muls_per_lane() -> int:
    return POINT_ADDS * MULS_PER_ADD + MULS_INVERT_ENCODE


def operations(lanes: int, message_bytes: int = 0, distinct_keys: int = 0) -> float:
    return float(lanes) * field_muls_per_lane() * OPS_PER_FIELD_MUL


def bytes_moved(lanes: int, message_bytes: int = 0, distinct_keys: int = 0) -> float:
    per_lane = 32 + 32 + 32 + 4 + message_bytes + 1 + WINDOWS * ENTRY_BYTES
    return float(lanes) * per_lane


def least_seconds(lanes: int, message_bytes: int, distinct_keys: int,
                  peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = operations(lanes, message_bytes, distinct_keys) / peaks["flops_per_s"]
    t_mem = bytes_moved(lanes, message_bytes, distinct_keys) / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
