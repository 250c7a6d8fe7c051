"""The repo's benchmark: one command runs one cell (a model
configuration under a traffic mix) once. See README.md."""
