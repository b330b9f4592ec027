"""repro.core — the ACCL+ collective engine, TPU/JAX-native.

Public API:
    CollectiveEngine     the CCLO: MPI-like + streaming collectives
    execute_program      the one data plane: runs a compiled micro-op Program
    Selector             runtime-tunable algorithm/protocol selection
    Communicator         rank group over a mesh axis
    Schedule/Step/Sel    microcode IR (compiles to a Program)
    Program              the micro-op IR (core/program.py)
    Sequencer/Request    the collective offload queue (engine.issue(...))
    PricingEnv           the one bundle of pricing parameters (env=)
    MeshMakespan         contention-aware composition of many queues
    FabricOccupancy      per-chip physical-link capacity map
    FaultPlan/ReliabilityTier  fabric fault model + protocol tiers
    register_collective  out-of-tree collectives, no engine changes needed
    Tracer/MetricsRegistry  unified telemetry (core/telemetry.py):
                         wall-clock control-plane spans on the
                         profiler's clock, virtual-clock traces, and
                         the stats registry
"""
from repro.core.engine import CollectiveEngine, execute_program
from repro.core.faults import (
    FaultPlan, FaultyTransport, PeerFailedError, ReliabilityTier, TIERS,
    TransportError, TransportTimeout,
)
from repro.core.mesh_cost import MeshMakespan
from repro.core.pricing import PricingEnv, resolve_env
from repro.core.program import Program, compile_schedule
from repro.core.plugins import register_collective, unregister_collective
from repro.core.selector import Selector, Choice
from repro.core.sequencer import Request, RequestCancelled, Sequencer
from repro.core.topology import (
    Communicator, FabricOccupancy, axis_comm, make_mesh,
)
from repro.core.schedule import Schedule, Step, Sel
from repro.core.hw_spec import HwSpec, TPU_V5E, ACCL_CLUSTER
from repro.core.telemetry import MetricsRegistry, NullTracer, StatsView, \
    Tracer
from repro.core import algorithms, faults, mesh_cost, plugins, pricing, \
    program, sequencer, simulator, telemetry

__all__ = [
    "CollectiveEngine", "execute_program", "Program", "compile_schedule",
    "register_collective", "unregister_collective", "Selector", "Choice",
    "Request", "RequestCancelled", "Sequencer",
    "PricingEnv", "resolve_env", "MeshMakespan", "FabricOccupancy",
    "FaultPlan", "FaultyTransport", "ReliabilityTier", "TIERS",
    "TransportError", "TransportTimeout", "PeerFailedError",
    "Communicator", "axis_comm", "make_mesh", "Schedule", "Step", "Sel",
    "HwSpec", "TPU_V5E", "ACCL_CLUSTER",
    "Tracer", "NullTracer", "MetricsRegistry", "StatsView",
    "algorithms", "faults",
    "mesh_cost", "plugins", "pricing", "program", "sequencer", "simulator",
    "telemetry",
]
