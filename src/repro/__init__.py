"""repro: OpenCL-actor-style data-parallel runtime + LM framework in JAX.

Paper: "OpenCL Actors — Adding Data Parallelism to Actor-based Programming
with CAF" (Hiesgen, Charousset, Schmidt; Agere/LNCS 2017), adapted to
JAX/TPU. See DESIGN.md.
"""
__version__ = "0.1.0"
