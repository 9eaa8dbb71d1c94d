"""Distribution layer: collectives + sharding specs for the production meshes.

Two submodules, mirroring the paper's split between *how partial sums move*
and *where tensors live*:

* :mod:`repro.dist.collectives` — the H-tree all-reduce (log-depth pairwise
  tree reduction, the SPMD analogue of the die-level H-tree bus of
  ``core/htree.py``) plus the generic ``allreduce`` reducer hook that the
  model stack threads through ``Runtime.collective``.
* :mod:`repro.dist.sharding` — ``NamedSharding``/``PartitionSpec`` builders
  for params, inputs and decode state on the ``(data, model)`` (and
  ``(pod, data, model)``) meshes, including the three resident-expert
  serve layouts (``ep2`` / ``ep_data`` / ``etp2``).
"""
from repro.dist import collectives, sharding  # noqa: F401
