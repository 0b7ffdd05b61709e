"""A kernel's logical operations and bytes, computed from its shapes."""
