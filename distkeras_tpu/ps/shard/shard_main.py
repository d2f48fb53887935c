"""One shard-server OS process: ``python -m distkeras_tpu.ps.shard.shard_main SPEC``.

The deployment shape of a sharded parameter server is a FLEET — one
single-shard server per process (per host, at scale), exactly like the
reference's parameter-server processes (Li et al., OSDI'14).  This module
is that process: it rebuilds the center from a spec file, derives the
shard plan deterministically (the same pure function every worker runs),
hosts ITS slice behind a :class:`~.server.ShardFrontend`, writes the
bound port to ``port_file`` for the spawner, and serves until killed.

The spec is a msgpack tree (``utils.serde``)::

    {"center_blob": tree_to_bytes(full center tree),
     "num_shards": int, "shard_index": int, "epoch": int,
     "ps_class": "delta" | "adag" | "dynsgd",
     "num_workers": int, "host": str (default 127.0.0.1),
     "port": int (0 = ephemeral), "port_file": path}

Used by :class:`~.server.ProcessShardFleet` (the bench's
``--ps-shard-placement processes`` mode); also runnable by hand for a
manual multi-host fleet — same spec on every host, ``shard_index``
varied.
"""

from __future__ import annotations

import os
import sys
import time


def run_spec(spec_path: str) -> None:
    # shard servers are pure host-side processes: never grab a device
    # (set before anything below imports JAX)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ...utils import serde
    from ..servers import (ADAGParameterServer, DeltaParameterServer,
                           DynSGDParameterServer)
    from .plan import ShardPlan
    from .server import ShardFrontend

    classes = {"delta": DeltaParameterServer, "adag": ADAGParameterServer,
               "dynsgd": DynSGDParameterServer}
    with open(spec_path, "rb") as f:
        spec = serde.tree_from_bytes(f.read())
    center = serde.tree_from_bytes(spec["center_blob"])
    plan = ShardPlan.build(center, int(spec["num_shards"]),
                           epoch=int(spec.get("epoch", 0)))
    i = int(spec["shard_index"])
    ps = classes[spec.get("ps_class", "delta")](
        plan.split(center)[i], num_workers=int(spec.get("num_workers", 1)))
    server = ShardFrontend(ps, plan, i,
                           host=spec.get("host", "127.0.0.1"),
                           port=int(spec.get("port", 0))).start()
    if spec.get("port_file"):
        tmp = spec["port_file"] + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        os.replace(tmp, spec["port_file"])  # atomic: spawner never
        #                                      reads a half-written port
    try:
        while True:  # serve until the spawner kills us
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()


def main(argv=None) -> int:
    from ...obs import emit
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) != 1:
        emit("usage: python -m distkeras_tpu.ps.shard.shard_main SPEC",
             err=True)
        return 2
    run_spec(argv[0])
    return 0


if __name__ == "__main__":
    sys.exit(main())
