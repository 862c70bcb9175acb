"""jaxlib private-API access, in one place.

The elastic control plane leans on jax's distributed-runtime service and
client, which live behind `jax._src` internals (`jax._src.lib._jax` in
the installed jaxlib 0.9.0). Touching them through this one module keeps
the degradation story (topology.recoverable_client_contract) honest:
when a jaxlib bump moves or re-signs them, there is one place to repair
and the callers' documented fallbacks take over meanwhile.
"""

from __future__ import annotations


def jaxlib_extension():
    """The jaxlib extension module. Raises ImportError if a jaxlib bump
    moved it — callers keep their own documented fallbacks."""
    from jax._src.lib import _jax
    return _jax


def make_distributed_service(address: str, num_nodes: int,
                             heartbeat_timeout: int,
                             shutdown_timeout: int):
    """Start a jax distributed-runtime (coordination) service."""
    return jaxlib_extension().get_distributed_runtime_service(
        address, num_nodes, heartbeat_timeout=heartbeat_timeout,
        shutdown_timeout=shutdown_timeout)


def make_distributed_client(coord: str, rank: int, init_timeout: int,
                            heartbeat_timeout: int, shutdown_timeout: int):
    """Construct (don't connect) the RECOVERABLE distributed-runtime
    client the elastic path wants: in-process reconnect after a peer
    failure, no all-task shutdown barrier, no destructor-time RPC.

    `jax.distributed.initialize()` cannot stand in for it while a
    launcher-owned service is up: on process 0 it starts a SECOND
    coordination service on the coordinator port. A TypeError here means
    the factory's signature drifted (see recoverable_client_contract).
    """
    return jaxlib_extension().get_distributed_runtime_client(
        coord, rank, init_timeout=init_timeout,
        heartbeat_timeout=heartbeat_timeout,
        shutdown_timeout=shutdown_timeout,
        use_compression=True, recoverable=True,
        shutdown_on_destruction=False)
