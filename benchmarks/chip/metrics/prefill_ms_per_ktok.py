"""Device time of the prefill/extend program per 1000 prompt tokens it
computed, over the traced slice."""
import readers

read = readers.prefill_ms_per_ktok
