"""Traffic generators: every mix in this directory is a data file of
parameters (``<traffic>.json``) that one of these generators reads."""
