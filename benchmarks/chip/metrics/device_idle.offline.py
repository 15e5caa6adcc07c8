"""Share of the traced slice, over steps with at least one slot active, in
which no operation ran on the device, in %."""
import readers

read = readers.device_idle
