from .ops import run_managed  # noqa: F401
