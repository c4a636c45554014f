"""Seconds of set-up spent building the mesh, the function space and the
assembly plan on the host (``core/mesh.py``, ``core/assembly.build_plan``)."""


def read(run):
    return run.spans.setup_s.get("mesh_plan")
