"""One module per model family: weights from the seed, the program's step,
batches and the count of required operations."""
