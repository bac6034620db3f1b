"""repro.launch — meshes, cell builders and the training entry point.

The package imports nothing itself: ``repro.launch.train`` does not load
the compile grid of ``repro.launch.steps``; import each module directly.
"""
