from . import autotune, ops, ref

__all__ = ["autotune", "ops", "ref"]
