"""Seeded open-loop traffic generators, one module per generator, and the
traffic mixes (``<mix>.json``) that parameterise them."""
