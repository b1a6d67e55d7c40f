"""Simulator: per cent of the image that one LOAD or STORE pass of the
64-PE fabric's VMEM-resident kernel covers, read as
``sim_image_pass_share.verify`` reads it (``pass_words`` over
``image_lanes`` of the window's kernel launches, step-weighted)."""
from bench.harness import load_reader

read = load_reader("sim_image_pass_share.verify")
