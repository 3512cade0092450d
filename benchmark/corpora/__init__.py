"""Corpus builders, one per file, found by ``corpus.builder``."""
