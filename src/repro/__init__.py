"""repro — Transparent accelerator dispatch for JAX at multi-pod scale.

A production-grade reproduction and TPU-native extension of
"Transparent FPGA Acceleration with TensorFlow" (Pfenning, Holzinger,
Reichenbach; 2021).
"""

__version__ = "1.0.0"
