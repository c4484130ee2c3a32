"""The chip benchmark of the federated QNN: ``python3 chipbench/run.py``."""
