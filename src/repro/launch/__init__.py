"""Launcher layer: mesh construction, config overrides, the compile
cache, and the train/serve/experiment entry points."""
