"""repro: ShmemJAX — ARL OpenSHMEM for Epiphany, rebuilt for TPU pods in JAX."""

__version__ = "1.0.0"
