#!/usr/bin/env python3
"""A node with the timed path broken underneath, for test_faults.py:

    faulty_node.py <fault> start --home ... --crypto-backend sidecar

``accept_all``  the verifier's answer is altered where it is produced:
                every lane of every batch comes back valid
``alter_value`` the app stores another value than the tx carries
``drop_half``   the app leaves out every second tx of a block
"""
import sys


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    if fault == "accept_all":
        from tmtpu.crypto import batch

        def all_valid(self, items, tally):
            return [True] * len(items), sum(it[3] for it in items)

        batch.SidecarBatchVerifier._verify_pending = all_valid
    else:
        from tmtpu.abci.example import kvstore

        real = kvstore.KVStoreApplication.deliver_tx
        n = [0]

        def deliver_tx(self, req):
            n[0] += 1
            if fault == "alter_value":
                req.tx = bytes(req.tx) + b"!"
            elif n[0] % 2:
                from tmtpu.abci import types as abci

                return abci.ResponseDeliverTx(code=abci.CODE_TYPE_OK)
            return real(self, req)

        kvstore.KVStoreApplication.deliver_tx = deliver_tx
    from tmtpu.cmd.__main__ import main as tm_main

    return tm_main(argv)


if __name__ == "__main__":
    sys.exit(main())
