"""One module per configuration: ``build_lanes(seeds, traffic, config)``."""
