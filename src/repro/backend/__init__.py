"""``repro.backend`` — pluggable execution engines behind one contract.

The paper's asyncMatMul/checkMatmul programming model is the seam that
lets one software stack target four CPUs; this package is that seam for
the reproduction.  One :class:`~repro.backend.base.Backend` protocol —
``dispatch(task, operands) -> handle``, ``check(handle)``,
``wait(handle)``, ``run_graph(TaskGraph)`` — with first-class
granularity (``tile | panel | layer``), epilogue fusion and a cluster
``units`` dimension, and six registered implementations:

=========================  =================================================
``get("jax")``             eager XLA execution (``AsyncMatmulEngine`` /
                           ``cute_matmul``) — numbers, no cycles
``get("pallas")``          the ``kernels/matmul`` fused Pallas kernel —
                           numbers via the grid-pipelined on-chip path
``get("desim")``           the discrete-event machine model — per-resource
                           timelines + Chrome traces, and (given operands)
                           the numbers from executing the *same* graph
``get("analytical")``      ``core.simulator`` closed forms — cycles only
``get("desim-cluster")``   N matrix units behind one shared, bandwidth-
                           partitioned loader (``sim.partition`` shards
                           the graph) — contended per-unit timelines
``get("sharded")``         the identical partitioned graph executed over
                           ``launch.mesh``/``shard_map`` — int8 bit-exact
                           against ``jax``
=========================  =================================================

Every front door goes through the registry: ``serving.ServingEngine``
lowers batch schedules here (``plan(units=N)`` prices them on contended
cluster timelines), ``benchmarks/run.py --engine``/``--units`` is a
registry lookup, ``set_default_matmul_backend`` sets the model zoo's
matmul route (held by ``core.fusion``, which imports nothing from this
package), and ``examples/sim_timeline.py`` /
``examples/cluster_scaling.py`` drive several backends with one graph.
A new engine is one ``@register`` away.

Typical use::

    from repro import backend
    from repro.core.task import MatMulTask

    b = backend.get("desim", granularity="panel")
    h = b.dispatch(MatMulTask(m=512, n=512, k=4096))      # asyncMatMul
    r = b.wait(h)                                         # checkMatmul
    r.cycles, r.timeline                                  # DES payload
"""

from repro.backend.base import (Backend, DispatchHandle, ExecResult,
                                MatMulOperands, NO_MATMUL_OPERANDS)
from repro.backend.registry import (ALIASES, available, dispatch_platform,
                                    get, get_tuned, matmul_backend_string,
                                    register, resolve,
                                    set_default_matmul_backend,
                                    set_dispatch_platform, set_tuned_dispatch,
                                    tuned_config, tuned_dispatch_enabled)

# Importing the implementation modules registers them.
from repro.backend.eager import JaxBackend, PallasBackend
from repro.backend.desim_backend import DESimBackend
from repro.backend.analytical_backend import AnalyticalBackend
from repro.backend.cluster_backend import ClusterDESimBackend
from repro.backend.sharded_backend import ShardedBackend

__all__ = [
    "Backend", "DispatchHandle", "ExecResult", "MatMulOperands",
    "NO_MATMUL_OPERANDS",
    "ALIASES", "available", "dispatch_platform",
    "get", "get_tuned", "matmul_backend_string", "register", "resolve",
    "set_default_matmul_backend", "set_dispatch_platform",
    "set_tuned_dispatch", "tuned_config", "tuned_dispatch_enabled",
    "JaxBackend", "PallasBackend", "DESimBackend", "AnalyticalBackend",
    "ClusterDESimBackend", "ShardedBackend",
]
