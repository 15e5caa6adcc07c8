"""Model FLOPs of every launch of the traced slice over device busy time at
the bf16 peak, in %: the whole step's share of the peak, beside the prefill
roofline that moves time to first token."""
import readers

read = readers.step_mfu
