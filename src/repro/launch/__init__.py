"""Launch helpers: the engine's worker mesh, the persistent compile cache
and the chip's published roofline peaks."""
