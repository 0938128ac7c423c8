"""One plain reference an architecture, found by the name a configuration's
``"reference"`` key gives (``gpt2.py`` states the contract)."""
