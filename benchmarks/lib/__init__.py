"""The benchmark's own yardstick: peaks, counts from shapes, weights
from a seed, the trace reduction and the plain references.  Nothing in
here imports the program."""
