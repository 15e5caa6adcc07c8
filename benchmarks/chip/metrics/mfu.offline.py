"""Model FLOPs of every launch of the window over the window's seconds at
the bf16 peak, in %."""
import readers

read = readers.window_mfu
