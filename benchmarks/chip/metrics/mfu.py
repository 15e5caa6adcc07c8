"""Model FLOPs of every launch of the traced slice over device busy time at
the bf16 peak, in %."""
import readers

read = readers.step_mfu
