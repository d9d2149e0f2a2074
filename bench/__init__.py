"""On-chip serving benchmark: cells of (model configuration x traffic mix),
driven through the paged serving engine. Entry: ``python3 bench/run.py``."""
