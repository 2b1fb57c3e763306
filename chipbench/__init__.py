"""On-chip benchmark of the Binary Bleed k-search (see ``run.py``)."""
