"""Distributed-training backends.

Where the reference's `_TorchBackend` wires NCCL process groups
(ref: python/ray/train/torch/config.py:112 `_setup_torch_process_group`,
:153 `on_start` picking nccl/gloo and MASTER_ADDR), the TPU-native backend
wires the JAX coordination service: rank-0's address becomes the
coordinator, every worker calls `jax.distributed.initialize`, and after
that a single `Mesh` spans all hosts' devices — collectives ride ICI
in-graph with no framework involvement.
"""
from __future__ import annotations

from typing import Dict, Optional


class Backend:
    """Hook interface (ref: train/backend.py BackendConfig/Backend split).

    `master_env` receives rank-0's (ip, port) with the port probed on
    rank-0's own host (WorkerGroup.master_addr) — a port free on the
    driver may be taken on the worker's host.
    """

    def master_env(self, master_ip: str, master_port: int) -> Dict[str, str]:
        return {}

    def on_start(self, rank: int, world_size: int,
                 master_env: Dict[str, str]) -> None:
        pass

    def on_shutdown(self) -> None:
        pass


class JaxBackend(Backend):
    """jax.distributed coordination across gang workers (multi-host SPMD)."""

    def master_env(self, master_ip: str, master_port: int) -> Dict[str, str]:
        return {"RTPU_JAX_COORDINATOR": f"{master_ip}:{master_port}"}

    def on_start(self, rank, world_size, master_env) -> None:
        if world_size <= 1:
            return
        import os

        import jax

        # CPU processes need the gloo collectives client — the default
        # CPU backend refuses multi-process computations. Decided from
        # the env var (not jax.default_backend(): querying it would
        # initialize backends BEFORE distributed.initialize, which
        # pins single-process topology). TPU keeps ICI collectives.
        if "cpu" in (os.environ.get("JAX_PLATFORMS") or ""):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=master_env["RTPU_JAX_COORDINATOR"],
            num_processes=world_size,
            process_id=rank)

    def on_shutdown(self) -> None:
        try:
            import jax

            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001
            pass


class TorchBackend(Backend):
    """CPU-torch gloo process group, for parity with reference TorchTrainer
    (ref: train/torch/config.py:156-162 backend choice; TPU path has no
    NCCL — torch here is for CPU-side preprocessing / baselines)."""

    def master_env(self, master_ip: str, master_port: int) -> Dict[str, str]:
        return {"MASTER_ADDR": master_ip, "MASTER_PORT": str(master_port)}

    def on_start(self, rank, world_size, master_env) -> None:
        import os

        import torch.distributed as dist

        os.environ.setdefault("MASTER_ADDR", master_env["MASTER_ADDR"])
        os.environ.setdefault("MASTER_PORT", master_env["MASTER_PORT"])
        if not dist.is_initialized():
            dist.init_process_group("gloo", rank=rank,
                                    world_size=world_size)

    def on_shutdown(self) -> None:
        try:
            import torch.distributed as dist

            if dist.is_initialized():
                dist.destroy_process_group()
        except Exception:  # noqa: BLE001
            pass


BACKENDS = {"jax": JaxBackend, "torch": TorchBackend, None: Backend}


def resolve_backend(name: Optional[str]) -> Backend:
    if isinstance(name, Backend):
        return name
    cls = BACKENDS.get(name)
    if cls is None:
        raise ValueError(f"unknown backend {name!r}; one of {list(BACKENDS)}")
    return cls()
