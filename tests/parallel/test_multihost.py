"""True multi-process multi-host bring-up: two OS processes rendezvous
through the control plane (LeaderBarrier), call ``jax.distributed.initialize``
against the leader's coordinator, build one global 2x4 CPU mesh spanning both
processes' devices, and run a sharded computation whose result every rank
must agree on (SURVEY.md §4 "multi-node without a cluster"; reference:
MultiNodeConfig lib/llm/src/engines.rs:44-60).
"""

import asyncio
import os
import sys
import textwrap
from pathlib import Path

import pytest

from dynamo_tpu.runtime.controlplane.server import ControlPlaneServer

RANK_SCRIPT = textwrap.dedent(
    """
    import asyncio, os, sys

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

    async def main():
        control_plane, rank, coord = sys.argv[1], int(sys.argv[2]), sys.argv[3]
        from dynamo_tpu.parallel.multihost import MultiNodeConfig, bootstrap_multihost
        from dynamo_tpu.runtime.distributed import DistributedRuntime
        from dynamo_tpu.utils.config import RuntimeConfig

        rt = await DistributedRuntime.create(RuntimeConfig(control_plane=control_plane))
        cfg = MultiNodeConfig(num_nodes=2, node_rank=rank, leader_addr=coord)
        await bootstrap_multihost(rt.plane.kv, cfg, timeout=90)

        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() == 8, jax.device_count()

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
        sharding = NamedSharding(mesh, PartitionSpec("dp", "tp"))
        # global [8, 8] array, value = global row index, sharded over both axes
        global_np = np.arange(8, dtype=np.float32)[:, None] * np.ones((8, 8), np.float32)
        arr = jax.make_array_from_callback(
            global_np.shape, sharding, lambda idx: global_np[idx]
        )
        total = jax.jit(
            lambda x: jnp.sum(x),
            out_shardings=NamedSharding(mesh, PartitionSpec()),
        )(arr)
        # sum of row indices over 8 columns: (0+..+7) * 8 = 224
        value = float(np.asarray(total))
        assert value == 224.0, value
        print(f"RANK_OK {rank} {value}", flush=True)
        await rt.close()

    asyncio.run(main())
    """
)


from tests.conftest import free_port as _free_port


@pytest.mark.integration
@pytest.mark.slow
async def test_two_process_multihost_mesh(tmp_path):
    server = ControlPlaneServer(port=0)
    await server.start()
    address = f"127.0.0.1:{server.port}"
    coord = f"127.0.0.1:{_free_port()}"

    repo_root = str(Path(__file__).parent.parent.parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "rank.py"
    script.write_text(RANK_SCRIPT)

    procs = []
    try:
        for rank in range(2):
            procs.append(
                await asyncio.create_subprocess_exec(
                    sys.executable, str(script), address, str(rank), coord,
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.PIPE,
                    env=env,
                )
            )
        outs = await asyncio.wait_for(
            asyncio.gather(*[p.communicate() for p in procs]), timeout=240
        )
        for rank, (out, err) in enumerate(outs):
            assert f"RANK_OK {rank} 224.0".encode() in out, (
                f"rank {rank} failed:\nstdout={out.decode(errors='replace')}\n"
                f"stderr={err.decode(errors='replace')[-3000:]}"
            )
    finally:
        for p in procs:
            if p.returncode is None:
                p.kill()
        await server.stop()
