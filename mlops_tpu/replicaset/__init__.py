"""Engine replica set (ISSUE 13): data-parallel serve fleet on a mesh.

One shm ring, E engine REPLICA processes: the router half lives here
(`ReplicaRouter`, consulted by every front end at submit time); the
transport half is the per-replica queue/doorbell/stats axes grown onto
`serve/ipc.py`; the process half is the supervisor forking E engine
children in `serve/frontend.py`. `replicaset.sim` builds an in-process
E-replica plane over simulated-device engines for the unit tests
(imported explicitly — it pulls serve.ipc, which this package's
import-light half must not).

Jax-free: front ends import the router; nothing here touches a device.
"""

from mlops_tpu.replicaset.router import ReplicaRouter

__all__ = ["ReplicaRouter"]
