"""The benchmark's yardstick: what later PRs may read and may not edit."""
