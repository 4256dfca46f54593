"""The yardstick: what later PRs may add to and may not change."""
