"""BASELINE config 1: 4-validator in-process testnet, kvstore ABCI app.

End-to-end: four real nodes (consensus + mempool reactors over pipe
switches) commit tx-bearing blocks; measures committed blocks/sec and
then asserts BYTE-IDENTICAL commit artifacts between the CPU and TPU
paths: for every committed block, the tx-merkle root, the part-set
header, and the commit verification verdicts are recomputed through the
TPU gateway and compared against the CPU reference.

Prints ONE JSON line. Run from the repo root.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tendermint_tpu.jitcache import enable as _enable_jit_cache
from tendermint_tpu.ops.gateway import platform_label

_enable_jit_cache()

N_BLOCKS = int(os.environ.get("BENCH_N_BLOCKS", "8"))
N_TXS = int(os.environ.get("BENCH_N_TXS", "64"))


def main() -> None:
    from tendermint_tpu.crypto import ed25519 as ed_cpu
    from tendermint_tpu.merkle.simple import simple_hash_from_hashes
    from tendermint_tpu.ops.gateway import Hasher, Verifier
    from tendermint_tpu.types import tx as tx_types
    from tests.test_reactors import (
        make_genesis,
        make_node,
        start_consensus_net,
        stop_net,
        wait_until,
    )
    from tendermint_tpu.abci.apps.kvstore import KVStoreApp

    nodes, switches = start_consensus_net(4, app_factory=KVStoreApp)
    t0 = time.perf_counter()
    try:
        for i in range(N_TXS):
            nodes[0].mempool.check_tx(b"bench%d=v%d" % (i, i))
        assert wait_until(
            lambda: all(n.store.height() >= N_BLOCKS for n in nodes), timeout=120
        ), [n.store.height() for n in nodes]
        elapsed = time.perf_counter() - t0

        # -- byte-identical commit artifacts: CPU vs TPU ------------------
        # honor an explicit disable (run_all's host run sets it);
        # the parity assertions hold either way — CPU fallback must be
        # byte-identical by design
        tpu_on = os.environ.get("TENDERMINT_TPU_DISABLE", "") != "1"
        verifier = Verifier(min_tpu_batch=1, use_tpu=tpu_on)
        hasher = Hasher(min_tpu_batch=1, use_tpu=tpu_on)
        part_size = nodes[0].state.params().block_gossip.block_part_size_bytes
        checked_sigs = 0
        for h in range(1, N_BLOCKS + 1):
            blocks = [n.store.load_block(h) for n in nodes]
            assert all(
                b.hash() == blocks[0].hash() for b in blocks
            ), f"nodes disagree at height {h}"
            blk = blocks[0]
            # tx root: CPU reference vs gateway kernel
            txs = blk.data.txs
            if txs:
                cpu_root = simple_hash_from_hashes(
                    [tx_types.tx_hash(t) for t in txs]
                )
                assert hasher.tx_merkle_root(list(txs)) == cpu_root == blk.header.data_hash
            # part-set header: CPU vs gateway kernel
            cpu_ps = blk.make_part_set(part_size)
            tpu_ps = blk.make_part_set(part_size, hasher=hasher.part_leaf_hashes)
            assert cpu_ps.header() == tpu_ps.header()
            # commit signatures: kernel verdicts == CPU verdicts
            commit = nodes[0].store.load_block_commit(h)
            if commit is None:
                continue
            vs = nodes[0].state.validators
            items = [
                (
                    vs.validators[i].pub_key.raw,
                    pc.sign_bytes(nodes[0].state.chain_id),
                    pc.signature.raw,
                )
                for i, pc in enumerate(commit.precommits)
                if pc is not None
            ]
            tpu_ok = verifier.verify_batch(items)
            cpu_ok = [ed_cpu.verify(p, m, s) for p, m, s in items]
            assert tpu_ok == cpu_ok and all(tpu_ok), f"verdict mismatch at {h}"
            checked_sigs += len(items)
    finally:
        stop_net(nodes, switches)

    print(
        json.dumps(
            {
                "metric": "testnet_blocks_per_sec",
                "value": round(N_BLOCKS / elapsed, 2),
                "unit": "blocks/s",
                "vs_baseline": 1.0,  # parity run: identical artifacts asserted
                "detail": {
                    "nodes": 4,
                    "app": "kvstore",
                    "blocks": N_BLOCKS,
                    "txs": N_TXS,
                    "commit_sigs_checked": checked_sigs,
                    "platform": platform_label(),
                    "parity": "byte-identical (tx roots, part headers, verdicts)",
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
