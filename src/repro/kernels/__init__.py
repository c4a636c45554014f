from .ops import batch_map_stiffness  # noqa: F401
