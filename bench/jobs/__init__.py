"""One job per kind of traffic mix."""
