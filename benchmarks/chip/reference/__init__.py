"""Plain float32 reference models, one module per model family. They import
nothing of the program and take nothing it made."""
