"""repro — WoW (window-to-window RFANNS) reproduction on jax/Pallas.

Everything lives in subpackages.
"""
