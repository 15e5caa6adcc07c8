"""Bytes the decode launches of the traced slice need (weights once per
step, KV at live lengths, new rows), over HBM bandwidth and their device
time, in %."""
import readers

read = readers.decode_hbm_roofline
