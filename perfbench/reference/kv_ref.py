"""Plain key-value semantics of the `signedkv` app: a tx is
pubkey(32) || signature(64) || payload, the payload is `key=value`, a tx
with a valid signature sets key to value (last write wins), any other tx
changes nothing. Python dict, hashlib via ed25519_ref; nothing of the
program.
"""

from __future__ import annotations

from . import ed25519_ref

SIG_TX_OVERHEAD = 96


def split_tx(tx: bytes):
    """(pubkey, payload, signature) or None for a tx too short."""
    if len(tx) <= SIG_TX_OVERHEAD:
        return None
    return tx[:32], tx[SIG_TX_OVERHEAD:], tx[32:SIG_TX_OVERHEAD]


def tx_valid(tx: bytes) -> bool:
    parts = split_tx(tx)
    return parts is not None and ed25519_ref.verify(*parts)


class KVReference:
    def __init__(self) -> None:
        self.state: dict[bytes, bytes] = {}

    def apply_payload(self, payload: bytes) -> None:
        if b"=" in payload:
            k, v = payload.split(b"=", 1)
        else:
            k, v = payload, payload
        self.state[k] = v

    def get(self, key: bytes) -> bytes:
        return self.state.get(key, b"")
