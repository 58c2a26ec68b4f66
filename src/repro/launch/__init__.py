"""Launchers: mesh construction, compile cache, train / serve CLIs."""
