"""One module per kind of objective.  Each defines ``Objective(config,
traffic, devices, seed)``: it makes the cell's data and weights from the
seed, builds the program's round, drives it, reads its state, runs the
plain reference and counts the work of a round."""
