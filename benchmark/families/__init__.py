"""One module per model family, found by a configuration's `family`: how the
program's state and batch are made from the seed for that family, its model
FLOPs per sample, and the comparison with its plain reference."""
