"""On-chip benchmark of the ifunc/X-RDMA runtime (see ``run.py``)."""
