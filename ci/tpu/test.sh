#!/usr/bin/env bash
# Accelerated end-to-end run (analog of ci/gpu/cuda_test.sh:29-42) on a
# machine with a TPU: chip_smoke.py polishes a simulated 2 Mbp assembly
# through the device aligner + device consensus and fails unless the run
# really happened on the chip (platform, probes, Mosaic dispatch counts,
# host rejects, quality vs truth, second-run byte identity). Inputs come
# from the seeded simulator; nothing is read from outside the checkout.
# One process per chip: run nothing else that needs the device beside it.
set -e
cd "$(dirname "$0")/../.."
python chip_smoke.py
