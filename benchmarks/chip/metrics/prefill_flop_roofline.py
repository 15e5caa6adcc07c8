"""FLOPs the prefill launches of the traced slice need, over the bf16 peak
and their device time, in %."""
import readers

read = readers.prefill_flop_roofline
