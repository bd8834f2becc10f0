"""Plain quorum check of one block's commit: from the commit as the
`commit` RPC gives it, the genesis validator set and the chain id, build
every precommit's canonical sign-bytes, verify every signature with
`ed25519_ref`, and say whether valid precommits for the block id hold
MORE than two thirds of the voting power. json, hashlib and
`ed25519_ref` alone; nothing of the program.

What a precommit signs (Tendermint v0.11 `types/canonical_json.go`):
compact JSON, keys in alphabetical order, byte strings as upper-case hex,

    {"chain_id":C,"vote":{"block_id":{"hash":H,"parts":{"hash":P,
     "total":n}},"height":h,"round":r,"type":2}}

A validator's address is ripemd160 of its type byte (1) and its 32 key
bytes; the set is ordered by address, and a precommit names its signer by
its index in that order and by the address.
"""

from __future__ import annotations

import hashlib
import json

from . import ed25519_ref

PRECOMMIT = 2
KEY_TYPE_ED25519 = 1


def validator_set(genesis: dict) -> list[dict]:
    """The genesis document's validators in the set's order (by address):
    [{"address": bytes, "pub_key": bytes, "power": int}]."""
    out = []
    for v in genesis["validators"]:
        typ, hexkey = v["pub_key"]
        if typ != KEY_TYPE_ED25519:
            raise ValueError(f"not an Ed25519 validator key: type {typ}")
        key = bytes.fromhex(hexkey)
        addr = hashlib.new("ripemd160", bytes([typ]) + key).digest()
        out.append({"address": addr, "pub_key": key, "power": int(v["power"])})
    return sorted(out, key=lambda v: v["address"])


def sign_bytes(chain_id: str, vote: dict) -> bytes:
    """The bytes a vote (as JSON) signs."""
    bid = vote["block_id"]
    parts = {"hash": bid["parts"]["hash"].upper(),
             "total": int(bid["parts"]["total"])}
    block_id = {"parts": parts}
    if bid["hash"]:                      # a nil vote leaves the hash out
        block_id["hash"] = bid["hash"].upper()
    obj = {"chain_id": chain_id,
           "vote": {"block_id": block_id, "height": int(vote["height"]),
                    "round": int(vote["round"]), "type": int(vote["type"])}}
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      ensure_ascii=True).encode()


def _same_block(a: dict, b: dict) -> bool:
    return (a["hash"].upper() == b["hash"].upper()
            and a["parts"]["hash"].upper() == b["parts"]["hash"].upper()
            and int(a["parts"]["total"]) == int(b["parts"]["total"]))


def check_commit(chain_id: str, validators: list[dict], height: int,
                 block_id: dict, commit: dict) -> dict:
    """Count the power of the valid precommits of `commit` for `block_id`
    at `height`. A lane is counted only if it is a precommit of that
    height for that block id, all lanes of one round, its signer stands at
    the index it names with the address it names, has not been counted
    before, and its signature verifies. {"quorum": bool, "power_valid",
    "power_total", "counted", "refused": [[lane, reason], ...]}."""
    total = sum(v["power"] for v in validators)
    refused: list[list] = []
    seen: set[int] = set()
    power = 0
    round_ = None
    for lane, pc in enumerate(commit.get("precommits") or []):
        if pc is None:
            continue
        why = None
        idx = pc.get("validator_index")
        if not isinstance(idx, int) or not 0 <= idx < len(validators):
            why = "no such validator"
        elif bytes.fromhex(pc["validator_address"]) != validators[idx]["address"]:
            why = "address does not stand at its index"
        elif idx != lane:
            why = "lane and index differ"
        elif idx in seen:
            why = "validator counted already"
        elif int(pc["type"]) != PRECOMMIT:
            why = "not a precommit"
        elif int(pc["height"]) != int(height):
            why = "another height"
        elif round_ is not None and int(pc["round"]) != round_:
            why = "another round"
        elif not _same_block(pc["block_id"], block_id):
            why = "another block id"
        else:
            sig = pc.get("signature")
            if not sig or sig[0] != KEY_TYPE_ED25519:
                why = "no Ed25519 signature"
            elif not ed25519_ref.verify(validators[idx]["pub_key"],
                                        sign_bytes(chain_id, pc),
                                        bytes.fromhex(sig[1])):
                why = "signature does not verify"
        if why is not None:
            refused.append([lane, why])
            continue
        if round_ is None:
            round_ = int(pc["round"])
        seen.add(idx)
        power += validators[idx]["power"]
    return {"quorum": 3 * power > 2 * total, "power_valid": power,
            "power_total": total, "counted": len(seen), "refused": refused}
