"""Photonic-rails reproduction package.

The pure-python layers (core/, sim/, benchmarks) import no jax, so the
simulator and benchmark entry points start without initializing a jax
backend.
"""
