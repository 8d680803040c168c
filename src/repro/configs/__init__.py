from .base import ARCHS, SHAPES, Arch, ShapeSpec, all_archs, cut_depth, get
