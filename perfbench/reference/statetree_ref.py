"""A read's proof under `signedkv`, checked plainly: hashlib's RIPEMD-160
and json, nothing of the program (`merkle/statetree_proof.py` is the
program's own check, and a run is held to this one).

What the app commits to. `signedkv`'s state is a treap of its records,
every node of it a (key, value) pair; the app hash a block's header
carries is the hash of the treap's root (the empty state's: no bytes).
Every hashed field is length-prefixed: the count of the length's
big-endian octets, then those octets (a length of 0 is the one octet
0), then the field's bytes. With H = RIPEMD-160:

    leaf(v)              = H(0x00 || field(v))
    node(k, vh, lh, rh)  = H(0x01 || field(k) || field(vh) || field(lh)
                               || field(rh))

where lh and rh are the hashes of the left and right subtrees (no bytes
for an empty one) and a key left of a node is smaller, as bytes, than
the node's.

What a read carries. `abci_query` with `prove` answers the record's
value, the height H it read at, and a proof: the hex of a JSON object
{"key", "steps", "value" (where the key is there)}, every step four hex
strings [key, value hash, left hash, right hash], the node that ends the
key's search path first and the root last. The header of height H + 1
carries the app hash of the state after block H.

The check: the proof is for the queried key; where the key is there its
first step is the node holding it, with the hash of the value the read
returned; where not, no step holds the key, the read returned nothing,
and the search dies at the first step in an empty subtree on the key's
side; every later step holds, on the key's side, the hash of the steps
below it; the last hash is the app hash.
"""

from __future__ import annotations

import hashlib
import json


def _h(data: bytes) -> bytes:
    return hashlib.new("ripemd160", data).digest()


def _field(b: bytes) -> bytes:
    n = len(b)
    if n == 0:
        return b"\x00" + b
    octets = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([len(octets)]) + octets + b


def leaf(value: bytes) -> bytes:
    return _h(b"\x00" + _field(value))


def node(key: bytes, vh: bytes, left: bytes, right: bytes) -> bytes:
    return _h(b"\x01" + _field(key) + _field(vh) + _field(left) + _field(right))


def proves(proof_hex: str, key: bytes, value: bytes, app_hash: bytes) -> bool:
    """Does the proof an `abci_query` answered (`proof_hex`) show that
    `key` held `value` (b"": that it was not there) under `app_hash`?"""
    try:
        obj = json.loads(bytes.fromhex(proof_hex))
        steps = [tuple(bytes.fromhex(x) for x in s) for s in obj["steps"]]
        if bytes.fromhex(obj["key"]) != key \
                or any(len(s) != 4 for s in steps):
            return False
        held = bytes.fromhex(obj["value"]) if "value" in obj else None
    except (ValueError, KeyError, TypeError):
        return False
    if not steps:
        return held is None and value == b"" and app_hash == b""
    k0, vh0, left0, right0 = steps[0]
    if held is not None:
        if held != value or k0 != key or vh0 != leaf(value):
            return False
    elif value != b"" or k0 == key or (left0 if key < k0 else right0) != b"":
        return False
    h = node(*steps[0])
    for k, vh, left, right in steps[1:]:
        if k == key or (left if key < k else right) != h:
            return False
        h = node(k, vh, left, right)
    return h == app_hash
