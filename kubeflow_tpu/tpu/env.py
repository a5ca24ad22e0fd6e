"""JAX/TPU environment: what a pod-slice worker is given, and what a process
that is about to use the chip checks and sets for itself.

The reference era injected free-form GPU env (``NVIDIA_VISIBLE_DEVICES``,
NCCL vars via images — example-notebook-servers/jupyter-pytorch/cuda.Dockerfile).
Here the coordinator bootstrap is *deterministic and computable at admission
time*: worker 0's address is the pod-0 DNS name of the workload's headless
Service (the same service-DNS scheme the reference culler uses to reach
notebooks — notebook-controller/pkg/culler/culler.go:138-144), and each
worker derives its process id from its StatefulSet ordinal at runtime.
Determinism matters because the PodDefault webhook rejects conflicting env
(reference: admission-webhook/main.go:152-187) — regenerating the same env
twice must be a no-op.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, List, Optional

from .topology import SliceTopology

ENV_COMPILE_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

JAX_COORDINATOR_PORT = 8476  # jax.distributed default
ENV_COORDINATOR_ADDRESS = "JAX_COORDINATOR_ADDRESS"
ENV_NUM_PROCESSES = "JAX_NUM_PROCESSES"
ENV_PROCESS_ID = "TPU_WORKER_ID"
ENV_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"


def coordinator_address(
    workload_name: str, namespace: str, cluster_domain: str = "cluster.local", port: int = JAX_COORDINATOR_PORT
) -> str:
    """pod-0 of the headless Service: <name>-0.<name>.<ns>.svc.<domain>:<port>."""
    return f"{workload_name}-0.{workload_name}.{namespace}.svc.{cluster_domain}:{port}"


def worker_hostnames(workload_name: str, namespace: str, num_hosts: int, cluster_domain: str = "cluster.local") -> str:
    return ",".join(
        f"{workload_name}-{i}.{workload_name}.{namespace}.svc.{cluster_domain}" for i in range(num_hosts)
    )


def jax_worker_env(
    topology: SliceTopology,
    workload_name: str,
    namespace: str,
    cluster_domain: str = "cluster.local",
    extra: Optional[Dict[str, str]] = None,
) -> List[Dict[str, str]]:
    """Env var list (pod-spec shape) making a pod a JAX TPU slice worker.

    ``TPU_WORKER_ID`` is left to runtime derivation from the StatefulSet
    ordinal (hostname suffix) by ``kubeflow_tpu.parallel.distributed`` —
    identical env on every pod keeps webhook injection deterministic.
    """
    env = {
        "JAX_PLATFORMS": "tpu",
        ENV_COORDINATOR_ADDRESS: coordinator_address(workload_name, namespace, cluster_domain),
        ENV_NUM_PROCESSES: str(topology.num_hosts),
        ENV_WORKER_HOSTNAMES: worker_hostnames(workload_name, namespace, topology.num_hosts, cluster_domain),
        "TPU_ACCELERATOR_TYPE": topology.accelerator.gke_name,
        "TPU_TOPOLOGY": topology.label,
        "TPU_CHIPS_PER_HOST": str(topology.chips_per_pod),
        "TPU_RUNTIME_METRICS_PORTS": "8431",
    }
    if extra:
        env.update(extra)
    return [{"name": k, "value": v} for k, v in sorted(env.items())]


def env_list_to_dict(env: List[Dict[str, str]]) -> Dict[str, str]:
    return {e["name"]: e.get("value", "") for e in env}


def require_tpu() -> Any:
    """The first JAX device, which must be a TPU. Benchmarks, the chip smoke
    and anything else whose output is read as a device result call this
    before building a model: on a CPU they stop here instead of printing a
    row nobody can tell from a chip's."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0] is {device.platform!r} "
            f"({device.device_kind}); this entry point runs on the chip only")
    return device


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside and JAX
    reads it itself, so nothing is set in code. Unset, the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key and a directory that moves never hits. From here on
    ``profiling.watch_compiles()`` says which programs were compiled and
    which loaded."""
    import jax

    from . import profiling

    profiling.watch_compiles()
    path = os.environ.get(ENV_COMPILE_CACHE_DIR)
    if not path:
        path = str(Path(__file__).resolve().parents[2] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
