from . import synthetic
