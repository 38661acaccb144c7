from .mesh import MeshPlan, make_mesh  # noqa: F401
from .sharding import params_pspec_tree, shard_params  # noqa: F401
