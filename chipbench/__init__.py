"""The chip benchmark of the ChemGCN system: one run of one cell per
``python3 chipbench/run.py`` call (see ``run.py``)."""
