"""Plain references, one module per model family, named by the
configuration (``reference``) or the mix that uses them."""
