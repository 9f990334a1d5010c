"""Plain float32 reference of the served configurations: weights drawn from
the seed without the program, and the forward pass the served tokens are
checked against."""
