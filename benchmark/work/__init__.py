"""One module of work counts an architecture, found by the name a
configuration's ``"work"`` key gives (``gpt2.py`` states the contract)."""
