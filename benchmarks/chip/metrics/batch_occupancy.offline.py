"""Mean active slots over slots, over the decode iterations of the window
(the engine's serve.decode_iter span), in %."""
import readers

read = readers.batch_occupancy
