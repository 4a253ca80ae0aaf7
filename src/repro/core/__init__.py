"""The paper's contribution: OpenCL-style kernel actors for JAX/TPU.

The surface is declarative — signature and index space are captured at
definition site, composition is a builder, pooling is one call:

    from repro.core import ActorSystem, NDRange, In, Out, dim_vec, kernel

    @kernel(In(jnp.float32), In(jnp.float32),
            Out(jnp.float32, shape=(n, n)),
            nd_range=NDRange(dim_vec(n, n)))
    def m_mult(a, b):
        return a @ b

    sys_ = ActorSystem()
    worker = sys_.spawn(m_mult)
    result = worker.ask(a, b)

    pipe = Pipeline(sys_, mode="auto").stage(m_mult).stage(scale).build()
    pool = sys_.opencl_manager().spawn_pool(m_mult, 4, policy="least_loaded")

Non-linear compositions use the typed DAG builder (``repro.core.Graph``):
nodes are kernels/actors/Python stages, edges are shape/dtype-checked
ports, and ``build()`` validates the topology before spawning — see the
README "Dataflow graphs" section and ``examples/graph_diamond.py``.
"""
from .actor import Actor, ActorRef, ActorSystem, Message
from .api import ActorPool, KernelDecl, Pipeline, kernel
from .errors import (AccessViolation, ActorError, ActorFailed,
                     ArityMismatchError, DanglingPortError, DeadlineExceeded,
                     DownMessage, ExitMessage, GraphCycleError, GraphError,
                     MailboxClosed, PortTypeMismatchError, SignatureMismatch)
from .facade import KernelActor
from .graph import Graph, GraphNode, GraphPlan, GraphRef, Port, PortType
from .manager import Device, DeviceManager, Platform
from .memref import (DeviceRef, RefRegistry, as_device_array, live_ref_count,
                     memory_stats, payload_nbytes, reset_transfer_stats,
                     transfer_count, tree_release, tree_unwrap, tree_wrap)
from .placement import (NodeTarget, PlacementDecision, PlacementService,
                        WireCostModel)
from .placement import service as placement_service
from .placement import set_service as set_placement_service
from .scheduler import ChunkScheduler, split_offload
from .signature import In, InOut, KernelSignature, Local, NDRange, Out, Priv, dim_vec

__all__ = [
    "Actor", "ActorRef", "ActorSystem", "Message",
    "ActorPool", "KernelDecl", "Pipeline", "kernel",
    "AccessViolation", "ActorError", "ActorFailed", "ArityMismatchError",
    "DanglingPortError", "DeadlineExceeded", "DownMessage", "ExitMessage",
    "GraphCycleError", "GraphError", "MailboxClosed",
    "PortTypeMismatchError", "SignatureMismatch",
    "KernelActor",
    "Graph", "GraphNode", "GraphPlan", "GraphRef", "Port", "PortType",
    "Device", "DeviceManager", "Platform",
    "DeviceRef", "RefRegistry", "as_device_array", "live_ref_count",
    "memory_stats", "reset_transfer_stats", "transfer_count",
    "tree_release", "tree_unwrap", "tree_wrap",
    "ChunkScheduler", "split_offload",
    "NodeTarget", "PlacementDecision", "PlacementService", "WireCostModel",
    "placement_service", "set_placement_service", "payload_nbytes",
    "In", "InOut", "KernelSignature", "Local", "NDRange", "Out", "Priv", "dim_vec",
]
