#!/bin/sh
# Runs each cell end to end on the CPU at a tiny archive and one layer of
# depth; prints counts only (see rehearse.py). From the root of the repo.
JAX_PLATFORMS=cpu exec python3 -m chipbench.rehearse "$@"
