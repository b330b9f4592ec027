# NOTE: do not import repro.launch.dryrun here — it sets XLA device-count
# flags at import time and must only be imported as a fresh __main__.
from repro.launch.cache import configure_compile_cache
from repro.launch.mesh import make_production_mesh, make_mesh_for

__all__ = ["configure_compile_cache", "make_production_mesh",
           "make_mesh_for"]
