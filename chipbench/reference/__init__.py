"""Plain float32 references of the benchmark's models. They import
nothing of the program: weights are made again from the seed, by the
model's published initialisation as the program applies it."""
