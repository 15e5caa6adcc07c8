"""Device time per decode launch over the traced slice, in ms."""
import readers

read = readers.decode_step_ms
