"""The chip benchmark: cells, traffic, references and metric readers."""
