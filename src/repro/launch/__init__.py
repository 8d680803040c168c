"""Launchers: production mesh, multi-pod dry-run, train/serve drivers.

NOTE: ``dryrun`` must be imported first in its process (it pins
XLA_FLAGS for 512 placeholder CPU devices) — do not import it from tests.
"""
from .mesh import make_local_mesh, make_production_mesh
